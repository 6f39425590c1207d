"""launch/profiles.py: tuned flag profiles resolve and parse cleanly."""
import pytest

from repro.common.perf import PerfFlags
from repro.configs import ARCH_IDS
from repro.common.config import INPUT_SHAPES
from repro.launch.profiles import BASE_PERF, PAIR_OVERRIDES, resolve


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("shape", list(INPUT_SHAPES))
def test_resolve_parses_for_every_pair(arch, shape, monkeypatch):
    perf, strategy = resolve(arch, shape)
    flags = PerfFlags().apply_overrides(perf)    # must not raise
    assert flags.attn_chunk_remat == "on"
    import os
    # importing dryrun appends a 512-device flag for its own command
    # line: keep it out of any later JAX start in this process
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    from repro.launch.dryrun import parse_strategy
    parse_strategy(strategy)                     # must not raise


def test_pair_overrides_win_over_base():
    perf, strategy = resolve("qwen1.5-110b", "prefill_32k")
    assert PerfFlags().apply_overrides(perf).attn_constraint == "off"
    perf, strategy = resolve("gemma2-2b", "prefill_32k")
    assert PerfFlags().apply_overrides(perf).attn_chunk == 4096
    assert "prefill_seq_axis=model" in strategy


def test_moe_archs_get_shard_map():
    for arch in ("kimi-k2-1t-a32b", "arctic-480b"):
        perf, _ = resolve(arch, "train_4k")
        assert PerfFlags().apply_overrides(perf).moe_dispatch == "shard_map"
    perf, _ = resolve("gemma2-2b", "train_4k")
    assert PerfFlags().apply_overrides(perf).moe_dispatch == "einsum"


def test_overrides_reference_known_pairs():
    for arch, shape in PAIR_OVERRIDES:
        assert arch in ARCH_IDS
        assert shape in INPUT_SHAPES
