"""Known-clean fixture for the pallas kernel contract checker: correct
prefetch-aware index_maps, fp32 scratch, fp32-promoted softmax,
operand order, dimension_semantics. The analyzer must report nothing
here. Never imported at runtime — parsed only.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(kvlen_ref, x_ref, o_ref, acc_scr, *, nk):
    x = x_ref[...].astype(jnp.float32)
    acc_scr[...] = jnp.exp(x)
    o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def clean_call(x, kv_len):
    kernel = functools.partial(_kernel, nk=4)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(4, 4),
        in_specs=[
            pl.BlockSpec((None, 128), lambda b, k, kvl: (b, 0)),
        ],
        out_specs=pl.BlockSpec((None, 128), lambda b, k, kvl: (b, 0)),
        scratch_shapes=[
            pltpu.VMEM((8, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((4, 128), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=True,
    )(kv_len, x)
