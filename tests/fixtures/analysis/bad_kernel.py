"""Seeded-bad fixture for the pallas kernel contract checker
(RL201-RL205), written in the repo kernels' idiom (local grid_spec +
functools.partial kernel binding) so the checker's Name resolution is
exercised.

Each `# expect: RL###` marker pins the exact line the analyzer must
report. Never imported at runtime — parsed only.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(s_ref, x_ref, o_ref):                  # expect: RL203
    p = jnp.exp(x_ref[...])                        # expect: RL205
    o_ref[...] = p.astype(o_ref.dtype)


def bad_call(x, s):
    kernel = functools.partial(_kernel)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(4, 2),
        in_specs=[
            pl.BlockSpec((None, 8), lambda i, j: (i, 0)),   # expect: RL202
        ],
        out_specs=pl.BlockSpec((None, 8), lambda i, j, s0: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((8, 8), jnp.bfloat16),               # expect: RL201
        ],
    )
    out = pl.pallas_call(                          # expect: RL203, RL204
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((4, 8), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=True,
    )(x)
    return out
