"""Row-wise int8 quantization Pallas kernel.

The Puzzle Worker (de)quantizes tensors at subgraph dtype boundaries
(paper §5.1); this kernel fuses absmax + scale + round into one VMEM pass
per (block_rows, cols) tile. Symmetric per-row scaling:
``q = round(x / scale)``, ``scale = absmax / 127``.

Oracle: ``repro.kernels.ref.quantize_ref``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _quant_kernel(x_ref, q_ref, scale_ref):
    x = x_ref[...].astype(jnp.float32)                  # (rows, cols)
    absmax = jnp.max(jnp.abs(x), axis=1)                # (rows,)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x / scale[:, None]), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    scale_ref[...] = scale.astype(jnp.float32)


def quantize_int8(
    x: jnp.ndarray,                  # (R, C)
    *,
    block_rows: int = 256,
    interpret: bool = False,
):
    """Returns (q int8 (R, C), scale f32 (R,))."""
    r, c = x.shape
    block_rows = min(block_rows, r)
    nr = -(-r // block_rows)
    pad = nr * block_rows - r
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)), constant_values=1.0)
    q, scale = pl.pallas_call(
        _quant_kernel,
        grid=(nr,),
        in_specs=[pl.BlockSpec((block_rows, c), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
            pl.BlockSpec((block_rows,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nr * block_rows, c), jnp.int8),
            jax.ShapeDtypeStruct((nr * block_rows,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=interpret,
    )(x)
    return q[:r], scale[:r]


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale[:, None]
