"""A run whose timed path is broken underneath reads ``correct`` false:
one case for each fault a served cell can have. (The replicas of the
four-chip cell exchange nothing between chips, so there is no exchange
to leave out.)"""
import json
import os
import shutil

import jax.numpy as jnp
import pytest

import repro.serving.engine as engine_mod
from bench.tests.rehearse import run_cell

_decode = engine_mod.decode_step
_sample = engine_mod.sample


def state_unchanged(params, cfg, cache, batch, backend=None):
    logits, _ = _decode(params, cfg, cache, batch, backend=backend)
    return logits, cache


def half_batch_left_out(params, cfg, cache, batch, backend=None):
    """Every other slot is left out and served its neighbour's row."""
    logits, new = _decode(params, cfg, cache, batch, backend=backend)
    return jnp.repeat(logits[0::2], 2, axis=0)[:logits.shape[0]], new


def token_altered(logits, rng, cfg):
    return (_sample(logits, rng, cfg) + 1) % logits.shape[-1]


FAULTS = {"state_unchanged": ("decode_step", state_unchanged),
          "half_batch_left_out": ("decode_step", half_batch_left_out),
          "token_altered": ("sample", token_altered)}


ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def busy_agent_cell(tmp_path):
    """The agent cell's files, offered fast enough at smoke size that
    several slots decode together."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (tmp_path / "bench" / "cells" / "qwen32b.agent.json").write_text(
        json.dumps({"rate_per_s": 3.0}))
    return tmp_path / "BENCHMARK.json"


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(fault, monkeypatch, capsys,
                                          tmp_path):
    name, broken = FAULTS[fault]
    monkeypatch.setattr(engine_mod, name, broken)
    result, _ = run_cell(capsys, "qwen32b.agent", seed=9, seconds=2.0,
                         benchmark=busy_agent_cell(tmp_path))
    assert result["correct"] is False
    gap = result["compared"]["logit_gap"]
    assert gap["value"] > gap["limit"]
