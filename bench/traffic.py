"""The benchmark's one traffic generator, driven by a mix file.

A mix file (``traffic/<name>.json``) gives the shape of the traffic; the
cell file (``cells/<workload>.json``) gives its rate. Two kinds exist:

* ``sessions`` -- agent sessions: each arrives on an open-loop Poisson
  stream and runs its turns closed-loop. Turn 1's prompt is its intent's
  shared prefix plus a query; each later turn appends the previous turn's
  served output and a tool result. The next turn is due a think time
  after the previous one finished.
* ``single`` -- unshared single-turn requests on an open-loop Poisson
  stream.

Fixed work per run. The sizes and the arrival times are drawn once
from the mix's ``pool_seed`` and are the same for every ``--seed``;
the seed draws the token ids of every prompt and prefix (and the
weights). Any reorder of the sessions changes which turns fall inside
a window as long as a few sessions, and with it the work there, so the
spread between seeds is the system's and not the traffic's.

Prompts are token-id lists, so the traffic does not move when the
program's serializer or tokenizer changes.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np


def _draw(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """``n`` integer sizes from a distribution spec of the mix file."""
    dist = spec["dist"]
    lo, hi = spec["lo"], spec["hi"]
    if dist == "uniform":
        out = rng.integers(lo, hi + 1, size=n)
    elif dist == "lognormal":
        out = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"],
                                size=n)).round()
    elif dist == "geometric":
        # support 1, 2, ...; mean 1/p
        out = rng.geometric(1.0 / spec["mean"], size=n)
    else:
        raise ValueError(f"unknown size distribution {dist!r}")
    return np.clip(out, lo, hi).astype(np.int64)


def _draw_float(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    dist = spec["dist"]
    if dist == "loguniform":
        return np.exp(rng.uniform(math.log(spec["lo"]), math.log(spec["hi"]),
                                  size=n))
    if dist == "fixed":
        return np.full(n, float(spec["value"]))
    raise ValueError(f"unknown time distribution {dist!r}")


def zipf_weights(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1) ** s
    return w / w.sum()


@dataclass
class Turn:
    """One request of a session: the sizes the pool fixed for it."""
    extra_tokens: int        # query (turn 1) or tool result (later turns)
    output_tokens: int
    think_s: float           # wait after the previous turn's finish


@dataclass
class Session:
    index: int
    arrival_s: float         # due time of turn 1, from the traffic start
    intent: Optional[int]    # index into ``Traffic.prefixes``; None: no prefix
    turns: List[Turn]


@dataclass
class Traffic:
    """A run's whole offered load, due times relative to traffic start."""
    sessions: List[Session]
    prefixes: List[List[int]]        # per intent: the shared prefix ids
    prefix_keys: List[str]           # per intent: its prefix-cache key
    token_lo: int
    token_hi: int
    max_context: int                 # prompt + longest output cap
    max_output: int
    seed: int

    def ids(self, session: int, turn: int, n: int, stream: int = 1
            ) -> List[int]:
        """The ``n`` new token ids of one turn's prompt: a function of
        the seed, the session and the turn alone, so the inputs do not
        depend on the order in which the run reaches them. The warm-up
        draws from streams of its own."""
        rng = np.random.default_rng([self.seed, stream, session, turn])
        return rng.integers(self.token_lo, self.token_hi, size=n).tolist()


def _pool(mix: dict, i: int, part: int) -> np.random.Generator:
    """Session ``i``'s own draws from the mix's pool: they depend on
    neither the rate nor the horizon nor the seed."""
    return np.random.default_rng([mix["pool_seed"], i, part])


def _unit_gap(mix: dict, i: int) -> float:
    """The arrival gap before session ``i``, at one arrival a second."""
    return float(_pool(mix, i, 0).exponential(1.0))


def _session(mix: dict, i: int, arrival_s: float) -> Session:
    rng = _pool(mix, i, 1)
    out_tok = mix["output_tokens"]
    if mix["kind"] == "single":
        turns = [Turn(int(_draw(rng, mix["prompt_tokens"], 1)[0]),
                      int(_draw(rng, out_tok, 1)[0]), 0.0)]
        return Session(i, arrival_s, None, turns)
    n = int(_draw(rng, mix["turns"], 1)[0])
    extra = np.concatenate([_draw(rng, mix["first_query_tokens"], 1),
                            _draw(rng, mix["tool_result_tokens"], n - 1)])
    outs = _draw(rng, out_tok, n)
    think = _draw_float(rng, mix["think_s"], n)
    n_int = len(mix["intents"])
    intent = int(rng.choice(n_int, p=zipf_weights(n_int, mix["zipf_s"])))
    return Session(i, arrival_s, intent,
                   [Turn(int(e), int(o), float(k))
                    for e, o, k in zip(extra, outs, think)])


def generate(mix: dict, rate: float, horizon_s: float, seed: int
             ) -> Traffic:
    """Draw the run's traffic: ``rate`` sessions (or requests) per second
    over ``horizon_s`` seconds of arrivals, their token ids from
    ``seed``."""
    kind = mix["kind"]
    if kind not in ("sessions", "single"):
        raise ValueError(f"unknown traffic kind {kind!r}")
    sessions: List[Session] = []
    t = 0.0
    while t < horizon_s:
        sessions.append(_session(mix, len(sessions), t))
        # a unit-rate gap of the pool, scaled to the rate
        t += _unit_gap(mix, len(sessions)) / rate
    rng = np.random.default_rng([seed, 0x7AFF1C])
    lo, hi = mix["token_ids"]
    specs = mix.get("intents", [])
    prefixes = [rng.integers(lo, hi, size=it["prefix_tokens"]).tolist()
                for it in specs]
    keys = [f"intent:{it['name']}" for it in specs]
    return Traffic(sessions, prefixes, keys, lo, hi, mix["max_context"],
                   mix["output_tokens"]["hi"], seed)


def describe(traffic: Traffic) -> Dict[str, float]:
    """Counts for the run's earlier lines."""
    turns = [t for s in traffic.sessions for t in s.turns]
    return {"sessions": len(traffic.sessions), "turns_drawn": len(turns),
            "output_tokens_drawn": sum(t.output_tokens for t in turns)}
