"""Bring-up smoke run of the GeckOpt serving path on a TPU.

  python chip_smoke.py               # one chip: phases (a), (b), (c)
  python chip_smoke.py --four-chips  # four chips: the replica cluster only

One chip serves planner-proxy-100m at its full published widths (random
weights from a fixed seed) through the entry points a user calls, with
the Pallas kernels compiled for the chip:

  (a) ``repro.launch.serve.main`` three times -- dense KV, paged KV with
      speculative decoding, dense KV with speculative decoding -- on
      prompts whose token lengths are not multiples of 8;
  (b) the GeckOpt pipeline demo (gate -> plan -> execute), whose
      sessions carry ~2.5k-token gated catalog prefixes through prefix
      prefill plus chunked extend;
  (c) prefill and first-decode-step logits of the pallas backend against
      the reference backend on the same prompts, within ``LOGIT_TOL``.

``--four-chips`` runs only a 4-replica ``EngineCluster`` under
intent-affinity routing, each replica committed to its own chip, and
checks its tokens against the same seeded workload on one chip.

Everything runs in this one process, which holds the chip(s). Without a
TPU the script exits non-zero before any phase. The last line of stdout
is a JSON object with ``ok`` and the device JAX reports; a failed phase
exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

ARCH = "planner-proxy-100m"
# prompt token lengths (with <bos>/<eos>): none a multiple of 8, one per
# flash_prefill tiling case -- a single block, a padded pair, and a
# many-block prompt just under the 1024-token attention chunk
PROMPT_LENS = (130, 300, 77, 1001)
# (c): |pallas - reference| over the logits, relative to the reference
# logits' largest magnitude. Both backends run bf16 weights and
# activations; they differ in fp32 accumulation order and in where
# activations are rounded back to bf16, and bf16 carries 8 mantissa
# bits (relative step 2**-8 ~ 0.4%). Through 12 layers such rounding
# moves a logit by a few steps; 5% of the largest logit leaves room for
# that and still fails a kernel that mis-tiles, mis-masks or drops a
# block, which moves logits by their own scale.
LOGIT_TOL = 0.05


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (its own
    monitoring events), so each phase can report set-up apart."""

    def __init__(self):
        self.secs = 0.0

    def __call__(self, event: str, duration: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.secs += duration


def make_prompt(n_tokens: int, seed: int) -> str:
    """Text that encodes to exactly ``n_tokens`` ids with specials: one
    id per short word, plus <bos> and <eos>."""
    from repro.serving.tokenizer import TOKENIZER
    text = " ".join(f"w{(seed * 7919 + 31 * i) % 997}"
                    for i in range(n_tokens - 2))
    assert len(TOKENIZER.encode_with_specials(text)) == n_tokens
    return text


def phase_prompts(n: int):
    return [make_prompt(PROMPT_LENS[i % len(PROMPT_LENS)], seed=i)
            for i in range(n)]


def report(name: str, device, t0: float, clock: CompileClock,
           c0: float, **fields):
    kind = device.device_kind
    extra = " | ".join(f"{k} {v}" for k, v in fields.items())
    print(f"[{name}] wall {time.time() - t0:.3f}s measured on "
          f"{device.platform}:{kind} | compile (set-up) "
          f"{clock.secs - c0:.3f}s | {extra}", flush=True)


class PhaseFailed(Exception):
    pass


def check(cond: bool, what: str):
    if not cond:
        raise PhaseFailed(what)


# ------------------------------------------------------------ phases ----

def phase_launcher(device, clock, *, smoke: bool, requests: int,
                   max_new: int):
    """(a) The serving launcher, three KV/decode modes."""
    from repro.launch import serve
    prompts = phase_prompts(requests)
    base = ["--arch", ARCH, "--backend", "pallas", "--max-batch", "4",
            "--cache-len", "2048", "--max-new", str(max_new),
            "--temperature", "0"] + (["--smoke"] if smoke else [])
    for p in prompts:
        base += ["--prompt", p]
    modes = {"dense": [],
             "paged+spec": ["--kv-mode", "paged", "--spec-decode"],
             "dense+spec": ["--spec-decode"]}
    first = None
    for mode, extra in modes.items():
        t0, c0 = time.time(), clock.secs
        out = serve.main(base + extra)
        check(out["finished"] == len(prompts),
              f"(a) {mode}: {out['finished']}/{len(prompts)} finished")
        tokens_out = sum(map(len, out["outputs"]))
        check(tokens_out >= len(prompts),
              f"(a) {mode}: only {tokens_out} tokens out")
        first = first or out["outputs"]
        same = sum(a == b for a, b in zip(first, out["outputs"]))
        report(f"a:{mode}", device, t0, clock, c0,
               requests=f"{out['finished']}/{len(prompts)} finished",
               tokens_out=tokens_out,
               same_tokens_as_dense=f"{same}/{len(prompts)}")


def phase_pipeline(device, clock, *, smoke: bool, sessions: int):
    """(b) The GeckOpt pipeline demo: gate -> plan -> execute."""
    spec = importlib.util.spec_from_file_location(
        "serve_pipeline", os.path.join(REPO, "examples",
                                       "serve_pipeline.py"))
    demo = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(demo)
    t0, c0 = time.time(), clock.secs
    out = demo.main(["--arch", ARCH, "--backend", "pallas",
                     "--requests", str(sessions), "--concurrency",
                     str(sessions)] + (["--smoke"] if smoke else []))
    es, ps = out["engine"], out["pipeline"]
    check(out["sessions"] == sessions,
          f"(b) {out['sessions']}/{sessions} sessions")
    check(ps["engine_turns"] == sessions,
          f"(b) {ps['engine_turns']} engine turns for {sessions} sessions")
    check(es["prefix_registrations"] > 0 and es["prefix_hits"] > 0,
          "(b) no prefix was registered and hit")
    check(es["tokens_generated"] > 0, "(b) no tokens decoded")
    report("b:pipeline", device, t0, clock, c0,
           sessions=f"{out['sessions']}/{sessions} finished",
           tokens_decoded=es["tokens_generated"],
           prefix_hits=es["prefix_hits"],
           prefill_tokens_saved=es["prefix_tokens_saved"])


def phase_parity(device, clock, *, smoke: bool, n_prompts: int):
    """(c) Pallas against reference logits on the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, get_smoke_config
    from repro.models.model import decode_step, init_params, prefill
    from repro.serving.tokenizer import TOKENIZER

    cfg = get_smoke_config(ARCH) if smoke else get_config(ARCH)
    params = init_params(jax.random.PRNGKey(0), cfg)
    cache_len = 2048
    steps = {be: (jax.jit(lambda p, b, be=be: prefill(
                      p, cfg, b, cache_len=cache_len, backend=be)),
                  jax.jit(lambda p, c, b, be=be: decode_step(
                      p, cfg, c, b, backend=be)))
             for be in ("reference", "pallas")}
    t0, c0 = time.time(), clock.secs
    worst, agree = 0.0, 0
    for text in phase_prompts(n_prompts):
        ids = jnp.asarray(TOKENIZER.encode_with_specials(text),
                          jnp.int32)[None]
        filled = {be: pf(params, {"tokens": ids})
                  for be, (pf, _) in steps.items()}
        # both backends decode the same next token: the reference's
        tok = jnp.argmax(filled["reference"][0], -1)[:, None]
        got = {be: [logits, steps[be][1](params, cache,
                                         {"tokens": tok.astype(jnp.int32)})[0]]
               for be, (logits, cache) in filled.items()}
        for ref, pal in zip(got["reference"], got["pallas"]):
            ref, pal = np.asarray(ref), np.asarray(pal)
            check(np.isfinite(pal).all(), "(c) non-finite pallas logits")
            rel = float(np.abs(pal - ref).max() / np.abs(ref).max())
            worst = max(worst, rel)
            agree += int((pal.argmax(-1) == ref.argmax(-1)).all())
    check(worst <= LOGIT_TOL,
          f"(c) max |dlogit| {worst:.6f} of the logit scale > {LOGIT_TOL}")
    report("c:parity", device, t0, clock, c0,
           prompts=n_prompts,
           max_logit_diff=f"{worst:.6f} of max|logit| (tol {LOGIT_TOL})",
           greedy_agreement=f"{agree}/{2 * n_prompts} (information)")


def phase_cluster(device, clock, *, smoke: bool, sessions: int):
    """--four-chips: 4 intent-affinity replicas, one per chip, against
    the same seeded workload with all 4 replicas on one chip."""
    import jax

    from repro.configs import get_config, get_smoke_config
    from repro.models.model import init_params
    from repro.serving.cluster import EngineCluster
    from repro.serving.workload import (WorkloadConfig, make_workload,
                                        register_workload_prefixes,
                                        uniform_mix)

    cfg = get_smoke_config(ARCH) if smoke else get_config(ARCH)
    params = init_params(jax.random.PRNGKey(0), cfg)
    reqs = make_workload(WorkloadConfig(
        n_sessions=sessions, intent_mix=uniform_mix(), max_turns=2,
        max_new_tokens=8, temperature=0.7, seed=0))
    outputs = {}
    for label, devs in (("4 chips", jax.devices()[:4]),
                        ("1 chip", jax.devices()[:1])):
        t0, c0 = time.time(), clock.secs
        cluster = EngineCluster(cfg, params, 4, router="intent_affinity",
                                max_batch=4, cache_len=512,
                                backend="pallas", devices=devs)
        placed = [{d for leaf in jax.tree.leaves(e.params)
                   for d in leaf.devices()} for e in cluster.replicas]
        want = [{devs[i % len(devs)]} for i in range(4)]
        check(placed == want, f"(4) {label}: replicas placed on {placed}")
        register_workload_prefixes(cluster, reqs)
        s = cluster.run_workload(reqs)
        summ = s.summary()
        check(summ["finished"] == len(reqs),
              f"(4) {label}: {summ['finished']}/{len(reqs)} finished")
        outputs[label] = s.outputs()
        report(f"4:cluster {label}", device, t0, clock, c0,
               requests=f"{summ['finished']}/{len(reqs)} finished",
               tokens_out=summ["tokens_out"],
               replica_devices=[sorted(d.id for d in p) for p in placed])
    check(outputs["4 chips"] == outputs["1 chip"],
          "(4) cluster tokens differ between 4 chips and 1 chip")
    print(f"[4:cluster] tokens identical across placements: "
          f"{len(outputs['1 chip'])} requests", flush=True)


def run(*, four_chips: bool, smoke: bool = False, requests: int = 8,
        max_new: int = 16, sessions: int = 8) -> None:
    """Run the phases; raises ``PhaseFailed`` on a failed check."""
    import jax
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    device = jax.devices()[0]
    if four_chips:
        phase_cluster(device, clock, smoke=smoke, sessions=2 * sessions)
        return
    phase_launcher(device, clock, smoke=smoke, requests=requests,
                   max_new=max_new)
    phase_pipeline(device, clock, smoke=smoke, sessions=sessions)
    phase_parity(device, clock, smoke=smoke,
                 n_prompts=min(requests, len(PROMPT_LENS)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-replica cluster path, one "
                         "replica per chip (needs 4 TPU chips)")
    args = ap.parse_args(argv)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    try:
        run(four_chips=args.four_chips)
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
