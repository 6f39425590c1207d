"""``models/qwen2.py`` computes what the dense-decoder code it replaced
computed, bit for bit: the weights of two seeds, the reference's and the
int8 control's gaps over one seeded sequence at smoke size, and the FLOP
counts at the full ``qwen1.5-32b-stage8`` shape. The pinned values were
read from that code on the CPU before it moved."""
import hashlib
import json
import os

import jax
import numpy as np
import pytest

from bench import spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
qwen2 = spec.model(BENCH, "qwen2")


def _conf():
    with open(os.path.join(BENCH, "configs", "qwen1.5-32b-stage8.json")) as f:
        return json.load(f)


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str((a.dtype, a.shape)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


WEIGHT_DIGESTS = {
    5: "93d7a2fe461501588dc81f0d7b64a0435a22497227c21023005c4ccbeaf8a406",
    3000000001:
        "9914d324c7e8a50d98128ae880aae9a336e9992040e091557ccf0fac9124f174"}


@pytest.mark.parametrize("seed", sorted(WEIGHT_DIGESTS))
def test_weights_are_the_same_draw(seed):
    s = qwen2.Shape.of(spec.smoke(_conf()))
    assert _digest(qwen2.make_weights(s, seed, eos_id=2)) \
        == WEIGHT_DIGESTS[seed]


def test_reference_and_control_gaps_are_the_same():
    s = qwen2.Shape.of(spec.smoke(_conf()))
    w = qwen2.make_weights(s, 5, eos_id=2)
    rng = np.random.default_rng(1234)
    seq = [int(t) for t in rng.integers(6, s.vocab, 600)]   # two chunks
    targets = [(p, seq[p + 1]) for p in range(300, 599, 7)]
    assert qwen2.sequence_gaps(w, s, seq, targets, 1024, control=True) \
        == (5.073494911193848, 0.12247228622436523)
    assert qwen2.sequence_gaps(w, s, seq, targets, 1024) \
        == (5.073494911193848, 0.0)


def test_flop_counts_at_full_shape_are_the_same():
    s = qwen2.Shape.of(_conf())
    assert qwen2.matmul_params(s) == 3966566400
    assert qwen2.attn_pair_flops(s) == 163840
    assert [qwen2.decode_token_flops(s, kv) for kv in (0, 1, 4096, 7168)] \
        == [7933132800, 7933296640, 8604221440, 9107537920]
