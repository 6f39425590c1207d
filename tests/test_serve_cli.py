"""Argparse smoke over launch/serve.py: flag combinations parse to the
expected namespaces, invalid combinations error out BEFORE any model is
built (SystemExit from argparse), and one tiny single-engine run plus
one tiny spec-decode cluster run exercise the two serving paths end to
end.
"""
import importlib.util
from pathlib import Path

import jax
import pytest

from repro.launch.serve import build_parser, main, validate_args


def parse(argv):
    ap = build_parser()
    return validate_args(ap, ap.parse_args(argv))


# ------------------------------------------------------------ parsing ----

def test_defaults():
    args = parse([])
    assert args.arch == "planner-proxy-100m"
    assert args.replicas == 1 and args.router == "intent_affinity"
    assert args.kv_mode == "dense"
    assert args.kv_blocks is None and args.block_size is None
    assert not args.spec_decode and args.draft_k == 4


@pytest.mark.parametrize("argv", [
    ["--replicas", "4", "--router", "least_loaded", "--profile",
     "bursty", "--skew", "0.7", "--turns", "2"],
    ["--kv-mode", "paged", "--kv-blocks", "64", "--block-size", "16"],
    ["--kv-mode", "paged"],                  # paged defaults are fine
    ["--backend", "pallas", "--spec-decode", "--draft-k", "2"],
    ["--spec-decode"],                       # default k
    ["--replicas", "2", "--spec-decode", "--kv-mode", "paged"],
    ["--skew", "1.0"],                       # boundary is valid
    ["--prompt", "a", "--prompt", "b"],
])
def test_valid_combinations_parse(argv):
    parse(argv)


@pytest.mark.parametrize("argv", [
    ["--kv-blocks", "64"],                   # paged-only kwarg on dense
    ["--block-size", "16"],
    ["--kv-mode", "dense", "--kv-blocks", "8"],
    ["--spec-decode", "--draft-k", "0"],     # spec decode with k < 1
    ["--spec-decode", "--draft-k", "-3"],
    ["--skew", "1.5"],                       # out of range
    ["--skew", "-0.1"],
    ["--replicas", "0"],
    ["--router", "bogus"],                   # argparse choices
    ["--kv-mode", "slab"],
    ["--backend", "cuda"],
    ["--profile", "steady"],
    ["--prompt", "a", "--replicas", "2"],    # cluster serves its workload
])
def test_invalid_combinations_error(argv):
    with pytest.raises(SystemExit):
        parse(argv)


# ------------------------------------------------------- tiny real runs ----

def test_single_engine_run(capsys):
    st = main(["--smoke", "--requests", "2", "--max-new", "2",
               "--max-batch", "2", "--cache-len", "128"])
    out = capsys.readouterr().out
    assert "served 2 requests" in out
    assert "kv[dense]" in out
    assert st["finished"] == st["requests"] == 2
    assert [len(o) for o in st["outputs"]] == [2, 2]


def test_single_engine_run_prints_its_waits(capsys):
    # one slot: the second request waits in the queue for the first
    st = main(["--smoke", "--requests", "2", "--max-new", "2",
               "--max-batch", "1", "--cache-len", "128"])
    out = capsys.readouterr().out
    assert st["queue_wait_p95_s"] > 0 and st["prefill_wait_p50_s"] > 0
    assert (f"p95 queue wait {st['queue_wait_p95_s'] * 1000:.0f}ms | "
            f"p50 prefill wait {st['prefill_wait_p50_s'] * 1000:.0f}ms"
            in out)


def test_prompts_from_the_command_line(capsys):
    words = " ".join(f"w{i}" for i in range(128))   # 130 ids with specials
    st = main(["--smoke", "--backend", "pallas", "--max-new", "2",
               "--max-batch", "2", "--cache-len", "256",
               "--prompt", words, "--prompt", "plot the bay"])
    assert "served 2 requests" in capsys.readouterr().out
    assert st["finished"] == 2 and len(st["outputs"]) == 2


def test_pipeline_demo_smoke_pallas(capsys):
    """examples/serve_pipeline.py end to end at smoke width on the
    pallas backend: gate -> plan -> execute, prefixes hit."""
    path = Path(__file__).resolve().parents[1] / "examples" / \
        "serve_pipeline.py"
    spec = importlib.util.spec_from_file_location("serve_pipeline", path)
    demo = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(demo)
    out = demo.main(["--smoke", "--backend", "pallas", "--requests", "2",
                     "--concurrency", "2"])
    assert "2 sessions in" in capsys.readouterr().out
    assert out["sessions"] == 2
    assert out["pipeline"]["engine_turns"] == 2
    assert out["engine"]["prefix_hits"] > 0


def test_compile_cache_placement(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and the code sets no other
    directory; without it the cache goes to the checkout's fixed,
    gitignored ``.jax_cache``."""
    from repro.launch import compile_cache as cc
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert cc.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = Path(__file__).resolve().parents[1]
        assert cc.enable_compile_cache() == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            repo / ".jax_cache")
        assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_cluster_spec_decode_run(capsys):
    main(["--smoke", "--replicas", "2", "--requests", "4",
          "--max-new", "4", "--max-batch", "2", "--cache-len", "128",
          "--temperature", "0.0", "--spec-decode", "--draft-k", "2",
          "--router", "intent_affinity", "--skew", "0.7"])
    out = capsys.readouterr().out
    assert "spec-decode[k=2]" in out
    assert "accept rate" in out