"""The ``decode_program_ms`` reader: on hand-made program runs, on the
trace directories the harness writes, and on the trace recorded on a
TPU v5e (``data/``), which predates the engine's program names, so the
reader finds nothing there while the device reduction reads what it
always read."""
import glob
import json
import os
from types import SimpleNamespace

import pytest

from bench import devtrace, spec

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READ = spec.reader(BENCH, "decode_program_ms")
MOD = READ.__globals__


def made():
    """Two chips; runs of 23 and 37 ns lie inside [100, 300), those
    across its edges do not."""
    return {0: [(90, 105, "jit_engine_decode(1)"),
                (122, 135, "jit_engine_extend(2)"),
                (147, 170, "jit_engine_decode(1)"),
                (295, 310, "jit_engine_decode(1)")],
            1: [(212, 249, "jit_engine_decode")]}


def test_program_ms_counts_whole_runs_in_the_window():
    program_ms = MOD["program_ms"]
    assert program_ms(made(), (100, 300)) == pytest.approx(1e-6 * 30)
    assert program_ms(made(), (100, 300), "jit_engine_extend") == \
        pytest.approx(1e-6 * 13)
    assert program_ms(made(), (100, 300), "jit_engine_prefill") is None
    # a program whose name only starts the same is another program
    assert program_ms({0: [(150, 160, "jit_engine_decoder")]},
                      (100, 300)) is None


def recorded():
    files = glob.glob(os.path.join(DATA, "*.xplane.pb"))
    if not files:
        pytest.fail("the recorded trace is missing from bench/tests/data")
    return files[0]


def test_recorded_trace_device_reduction_is_pinned():
    red = devtrace.reduce(devtrace.load(recorded()), [0])
    assert red.window_ns == 548877465
    assert red.busy_ns == {0: 330059233}
    assert 100 * red.idle_share[0] == pytest.approx(39.86649952917998)
    bd = devtrace.breakdown(red, 1)
    assert [n for n, _ in bd["device_ops"]] == [
        "%fusion.123 fusion bf16[48,4,8192,64]",
        "%fusion.127 fusion bf16[1,48,4,8192,64]",
        "%closed_call.9 tpu_custom_call bf16[48,4,3,64]",
        "%copy.60 copy bf16[1,48,4,8192,64]",
        "%copy.55 copy bf16[1,48,4,8192,64]",
        "%copy.62 copy bf16[1,48,4,8192,64]",
        "%copy.61 copy bf16[1,48,4,8192,64]",
        "%constant_dynamic-slice_fusion.16 fusion bf16[1,48,4,8192,64]",
        "%constant_dynamic-slice_fusion.13 fusion bf16[1,48,4,8192,64]",
        "%copy.3 copy bf16[12,48,4,8192,64]"]
    assert [s for _, s in bd["device_ops"]] == pytest.approx([
        0.058310152, 0.058301126, 0.042985357, 0.022777023, 0.022774182,
        0.021640688, 0.02163171, 0.014723405, 0.014721717, 0.014714465],
        abs=1e-9)
    assert bd["idle_gaps"] == [["bench.step: 727 gaps, longest 4.166 ms",
                                pytest.approx(0.218818232, abs=1e-9)]]


def test_recorded_trace_has_programs_under_other_names():
    mods = MOD["modules"](recorded())
    window = devtrace.window(devtrace.load(recorded()))
    assert list(mods) == [0] and mods[0]
    assert all(s < e for s, e, _ in mods[0])
    assert not any(n.startswith("jit_engine_") for _, _, n in mods[0])
    assert MOD["program_ms"](mods, window) is None


def checkout(tmp_path, monkeypatch, cells):
    """A checkout whose BENCHMARK.json lists ``cells`` for the metric,
    with the reader rooted there."""
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({"per_layer": [
        {"name": "decode_program_ms", "workloads": cells}]}))
    monkeypatch.setitem(MOD, "ROOT", str(tmp_path))


def trace_dir(tmp_path, cell):
    d = (tmp_path / ".bench_out" / "trace" / cell / "plugins" / "profile"
         / "t")
    d.mkdir(parents=True)
    return d


def test_reads_the_trace_of_the_runs_cell(tmp_path, monkeypatch):
    checkout(tmp_path, monkeypatch, ["a", "b"])
    # cell a's trace has a window of 200 ns, cell b's of 140
    for cell, close in (("a", 300), ("b", 240)):
        (trace_dir(tmp_path, cell) / "host.xplane.pb").write_text(
            str(close))
    tr = devtrace.Trace(host=[(100, 100, "bench.open")])

    def load(path):
        with open(path) as f:
            close = int(f.read())
        return devtrace.Trace(host=tr.host + [(close, close,
                                               "bench.close")])
    monkeypatch.setattr(devtrace, "load", load)
    monkeypatch.setitem(MOD, "modules", lambda path: made())
    assert READ(SimpleNamespace(trace=SimpleNamespace(window_ns=200))) \
        == pytest.approx(1e-6 * 30)
    # [100, 240): the run at 147-170 alone lies inside
    assert READ(SimpleNamespace(trace=SimpleNamespace(window_ns=140))) \
        == pytest.approx(1e-6 * 23)
    assert READ(SimpleNamespace(trace=None)) is None


def test_raises_when_no_trace_has_the_runs_window(tmp_path, monkeypatch):
    checkout(tmp_path, monkeypatch, ["a"])
    run = SimpleNamespace(trace=SimpleNamespace(window_ns=548877465))
    with pytest.raises(RuntimeError, match="no trace"):
        READ(run)                       # the directory is not there
    os.symlink(recorded(), trace_dir(tmp_path, "a") / "host.xplane.pb")
    with pytest.raises(RuntimeError, match="window of 548877464 ns"):
        READ(SimpleNamespace(trace=SimpleNamespace(window_ns=548877464)))


def test_reads_none_on_a_program_without_the_name(tmp_path, monkeypatch):
    checkout(tmp_path, monkeypatch, ["a"])
    os.symlink(recorded(), trace_dir(tmp_path, "a") / "host.xplane.pb")
    assert READ(SimpleNamespace(trace=SimpleNamespace(
        window_ns=548877465))) is None


def test_the_benchmark_lists_the_metrics_cells():
    assert MOD["_cells"]() == ["qwen32b.agent"]
