"""A new mix file and a new configuration file, in a directory of their
own, are found and run by name at smoke size -- with no code edit."""
import json
import os
import shutil

import pytest

from bench.tests.rehearse import run_cell

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONFIG = {
    "source": "https://example.org/tiny", "reduced": [],
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 2,
    "num_key_value_heads": 1, "head_dim": 32, "intermediate_size": 128,
    "vocab_size": 300, "tie_word_embeddings": False, "hidden_act": "silu",
    "attention_bias": True, "rope_theta": 5e5, "rms_norm_eps": 1e-5,
    "torch_dtype": "bfloat16", "attn_chunk": 64, "replicas": 1,
    "engine": {"backend": "pallas", "kv_mode": "dense", "max_batch": 3,
               "cache_len": 512, "prefill_budget": 64, "admission": "fifo",
               "router": None},
    "correct": {"logit_gap_limit": 0.05},
}
MIX = {
    "kind": "sessions", "pool_seed": 11,
    "intents": [{"name": "a", "prefix_tokens": 30},
                {"name": "b", "prefix_tokens": 50}],
    "zipf_s": 0.5,
    "turns": {"dist": "uniform", "lo": 1, "hi": 3},
    "think_s": {"dist": "fixed", "value": 0.05},
    "first_query_tokens": {"dist": "uniform", "lo": 5, "hi": 9},
    "tool_result_tokens": {"dist": "uniform", "lo": 5, "hi": 20},
    "output_tokens": {"dist": "uniform", "lo": 3, "hi": 6},
    "max_context": 200, "token_ids": [6, 300], "lead_in_s": 0.5,
}


@pytest.mark.parametrize("replicas", [1, 2])
def test_new_files_run_by_name(replicas, tmp_path, capsys):
    d = tmp_path / "mybench"
    for sub in ("configs", "traffic", "cells"):
        (d / sub).mkdir(parents=True)
    shutil.copytree(os.path.join(BENCH, "metrics"), d / "metrics")
    config = dict(CONFIG, replicas=replicas)
    if replicas > 1:      # the cluster path: EngineCluster.submit/step
        config["engine"] = dict(CONFIG["engine"], router="intent_affinity")
    (d / "configs" / "tiny.json").write_text(json.dumps(config))
    (d / "traffic" / "chatty.json").write_text(json.dumps(MIX))
    (d / "cells" / "tiny.chatty.json").write_text(
        json.dumps({"rate_per_s": 3.0}))
    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["mybench"],
        "run_seconds": 2,
        "configs": [{"name": "tiny", "source": "https://example.org/tiny",
                     "file": "mybench/configs/tiny.json", "reduced": [],
                     "why": "a test"}],
        "workloads": [{"name": "tiny.chatty", "config": "tiny",
                       "traffic": "chatty", "chips": 1, "why": "a test"}],
        "end_to_end": [
            {"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    result, lines = run_cell(capsys, "tiny.chatty",
                             benchmark=tmp_path / "BENCHMARK.json")
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["cpu_rehearsal"]) == {"ttft_p95_ms", "setup_s"}
    assert result["metrics"] == {}
    assert list(result)[-1] == "compared"
    assert any("compiles in window 0" in line for line in lines)


def _workloads():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("workload", _workloads())
def test_every_cell_finds_its_files_by_name(workload):
    from bench import spec
    cell = spec.find(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"),
                     workload)
    assert cell.cell["rate_per_s"] > 0 and cell.config["engine"]
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.reader(cell.data_dir, m["name"]))
