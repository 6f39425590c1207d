"""Shared Pallas TPU plumbing: compiler parameters and block tiling.

Mosaic (the TPU kernel compiler) takes a block whose last two dims are
multiples of the (8, 128) vreg tile or equal to the whole array dim.
``fit_block`` picks such a block for one axis and the padded length it
tiles; kernels pad that axis with ``pad_axis`` and mask or slice off the
pad rows, so any length compiles.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu


def compiler_params(*, dimension_semantics: Sequence[str]):
    """Mosaic compiler parameters: one semantics entry per grid axis
    ("parallel" or "arbitrary" = sequential)."""
    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics))


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def fit_block(n: int, max_block: int, align: int) -> Tuple[int, int]:
    """(block, padded length) for an axis of length ``n``.

    An axis that fits in ``max_block`` is one whole-dim block. A longer
    one is cut into the fewest blocks of at most ``max_block`` rows,
    each rounded up to a multiple of ``align``; the axis is padded to
    a whole number of them."""
    if n <= max_block:
        return n, n
    nb = _cdiv(n, max_block)
    block = _cdiv(_cdiv(n, nb), align) * align
    return block, nb * block


def pad_axis(x, axis: int, length: int):
    """Zero-pad ``x`` along ``axis`` at the end up to ``length``."""
    extra = length - x.shape[axis]
    if extra == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, extra)
    return jnp.pad(x, widths)
