"""The system under test behind one interface: one ``InferenceEngine``
(``add_request``/``step``) or an ``EngineCluster`` of one-chip replicas
(``submit``/``step``), as the configuration file's ``replicas`` says.

Each request carries the benchmark's own request index in the engine's
``session_id`` field, which the engine passes through untouched, so a
finished or live ``Request`` maps back to the benchmark's record on
every replica.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Sequence

#: engine counters the benchmark reads (summed over replicas)
COUNTERS = ("admissions", "prefix_hits", "prefix_tokens_saved",
            "decode_steps", "prefill_chunks", "prefills",
            "tokens_generated", "preemptions")


class Server:
    def __init__(self, cfg, params, engine_conf: Dict, replicas: int,
                 devices: Sequence, seed: int):
        from repro.serving.engine import InferenceEngine
        from repro.serving.sampling import SamplerConfig
        self.greedy = SamplerConfig(temperature=0.0)
        kw = dict(max_batch=engine_conf["max_batch"],
                  cache_len=engine_conf["cache_len"],
                  backend=engine_conf["backend"],
                  kv_mode=engine_conf["kv_mode"],
                  prefill_budget=engine_conf["prefill_budget"],
                  admission=engine_conf["admission"], seed=seed % 2**31)
        if replicas == 1:
            self.cluster = None
            self.engine = InferenceEngine(cfg, params, **kw)
            self.engines = [self.engine]
        else:
            from repro.serving.cluster import EngineCluster
            self.cluster = EngineCluster(
                cfg, params, replicas, router=engine_conf["router"],
                devices=list(devices), **kw)
            self.engines = self.cluster.replicas
        self.max_batch = kw["max_batch"]
        self.cache_len = kw["cache_len"]

    def register_prefix(self, key: str, ids: List[int]) -> None:
        (self.cluster or self.engine).register_prefix(key, ids)

    def prefix_home(self, key: str) -> int:
        """The replica that holds ``key``'s cached prefix."""
        if self.cluster is None:
            return 0
        return self.cluster.prefixes[key]

    def submit(self, index: int, prompt: List[int], max_new: int,
               prefix_key: Optional[str]) -> int:
        """Hand one request to the system; returns its replica."""
        if self.cluster is None:
            self.engine.add_request(prompt, max_new, self.greedy,
                                    prefix_key=prefix_key,
                                    session_id=index)
            return 0
        replica, _ = self.cluster.submit(prompt, max_new, self.greedy,
                                         prefix_key=prefix_key,
                                         session_id=index)
        return replica

    def step(self):
        """One step of the system; returns the requests it finished."""
        return (self.cluster or self.engine).step()

    def live(self) -> Iterator:
        """Requests holding a slot (decoding, or prefilling in chunks)."""
        for e in self.engines:
            for r in e.slots:
                if r is not None:
                    yield r

    def idle(self) -> bool:
        return all(e.is_idle() for e in self.engines)

    def counters(self) -> Dict[str, int]:
        return {k: sum(int(e.stats[k]) for e in self.engines)
                for k in COUNTERS}

    def reset(self) -> None:
        """Empty the system (slots, queues, counters, prefixes); its
        compiled programs stay."""
        (self.cluster or self.engine).reset()

    def close(self) -> None:
        """Drop every reference to the program's state, so the device
        memory it held is freed before the reference runs."""
        self.engine = self.cluster = None
        self.engines = []
