"""End-to-end system tests: the full GeckOpt pipeline in miniature —
Table-2 harness, benchmark scripts, neural gate, dry-run skip logic."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def test_table2_pipeline_small():
    from benchmarks.table2 import run
    out = run(n_tasks=48, tag="table2_test")
    for name, rec in out.items():
        assert 5 < rec["token_reduction_pct"] < 50, (name, rec)
        assert abs(rec["success_delta_pct"]) < 10


def test_steps_tools_pipeline():
    from benchmarks.steps_tools import run
    out = run(n_tasks=48)
    assert out["step_reduction_pct"] > 0
    assert out["tools_per_step_gain_pct"] > 0


def test_gating_sweep_monotone_fallback():
    from benchmarks.gating import run
    out = run(n_tasks=48)
    sw = out["sweep"]
    # lower gate accuracy => more fallbacks
    assert sw[0.5]["fallback_rate_pct"] >= sw[1.0]["fallback_rate_pct"]
    # perfect gate keeps success within noise
    assert abs(sw[1.0]["success_delta_pp"]) < 8


def test_neural_intent_classifier_smoke():
    """Untrained proxy scores intents (wiring test); training happens in
    examples/train_planner.py."""
    import jax
    from repro.configs import get_smoke_config
    from repro.core.intents import INTENTS
    from repro.models.model import init_params
    from repro.serving.neural_planner import NeuralIntentClassifier

    cfg = get_smoke_config("planner-proxy-100m")
    params = init_params(jax.random.PRNGKey(0), cfg)
    clf = NeuralIntentClassifier(cfg, params)
    intent, completion = clf.classify("plot images around Tampa Bay")
    assert intent in INTENTS


def test_dryrun_skip_logic(monkeypatch):
    from repro.common.config import INPUT_SHAPES
    from repro.configs import ARCH_IDS, get_config
    # importing dryrun appends a 512-device flag for its own command
    # line: keep it out of any later JAX start in this process
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    from repro.launch.dryrun import skip_reason

    long = INPUT_SHAPES["long_500k"]
    runs = [a for a in ARCH_IDS if not skip_reason(get_config(a), long)]
    skips = [a for a in ARCH_IDS if skip_reason(get_config(a), long)]
    assert set(runs) == {"hymba-1.5b", "xlstm-125m", "starcoder2-3b",
                         "gemma2-2b"}
    assert len(skips) == 6
    # every other shape runs everywhere
    for s in ("train_4k", "prefill_32k", "decode_32k"):
        assert all(not skip_reason(get_config(a), INPUT_SHAPES[s])
                   for a in ARCH_IDS)


def test_dryrun_artifacts_green_if_present():
    """If the committed dry-run sweep results exist, they must be clean."""
    import glob
    import json
    d = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")
    files = glob.glob(os.path.join(d, "*.json"))
    if not files:
        pytest.skip("dry-run sweep not yet executed")
    bad = []
    for f in files:
        with open(f) as fh:
            rec = json.load(fh)
        if rec["status"] == "error":
            bad.append(os.path.basename(f))
    assert not bad, bad
