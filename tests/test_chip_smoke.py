"""chip_smoke.py on the CPU: it refuses to run without a TPU, and its
phases pass at smoke width with the kernels in interpret mode (the
rehearsal of the chip run)."""
import importlib.util
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_exits_nonzero_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--four-chips"]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


@pytest.mark.parametrize("n", [77, 130, 300])
def test_prompts_have_exact_token_lengths(chip_smoke, n):
    from repro.serving.tokenizer import TOKENIZER
    ids = TOKENIZER.encode_with_specials(chip_smoke.make_prompt(n, seed=3))
    assert len(ids) == n


def test_phases_pass_at_smoke_width(chip_smoke, capsys):
    chip_smoke.run(four_chips=False, smoke=True, requests=2, max_new=3,
                   sessions=1)
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith("[")]
    assert [l.split("]")[0][1:] for l in lines] == [
        "a:dense", "a:paged+spec", "a:dense+spec", "b:pipeline",
        "c:parity"]
    assert all("measured on cpu:" in l for l in lines)
