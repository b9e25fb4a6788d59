"""Flash attention Pallas TPU kernel.

Grid: (batch×heads, Q blocks, KV blocks) with the KV dimension declared
``arbitrary`` (sequential) — the kernel revisits the same output block
across KV steps, carrying the online-softmax state (m, l, acc) in VMEM
scratch. BlockSpecs tile Q/K/V into (block_q, head_dim) / (block_k,
head_dim) VMEM tiles; head_dim and the block sizes are kept at multiples
of 128 so the MXU sees aligned matmuls.

Supports causal masking, GQA (KV-head index map = q_head // group_size)
and sliding-window masking (the `long_500k` dense path).

Oracle: ``repro.kernels.ref.attention_ref``; wrapper: ``repro.kernels.ops``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,            # VMEM tiles
    o_ref,                          # output tile
    m_scr, l_scr, acc_scr,          # scratch: (block_q,), (block_q,), (block_q, hd)
    *,
    scale: float,
    block_q: int,
    block_k: int,
    seq_q: int,
    seq_k: int,
    causal: bool,
    window: Optional[int],
    q_offset: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                  # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                          # (bq, bk)

    q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = k_pos < seq_k
    mask &= (q_pos - q_offset) < seq_q
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_scr[...] * alpha + p.sum(axis=-1)
    acc = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc / jnp.maximum(l_new, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,                     # (BH, Sq, hd)
    k: jnp.ndarray,                     # (BKv, Sk, hd)
    v: jnp.ndarray,
    *,
    q_heads_per_kv: int = 1,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused attention over flattened (batch×heads) leading dims.

    ``q_heads_per_kv``: GQA group size — row i of q maps to KV row
    ``i // q_heads_per_kv``.
    """
    bh, sq, hd = q.shape
    sk = k.shape[1]
    scale = hd ** -0.5
    block_q = min(block_q, max(sq, 8))
    block_k = min(block_k, max(sk, 8))
    nq = -(-sq // block_q)
    nk = -(-sk // block_k)
    pad_q = nq * block_q - sq
    pad_k = nk * block_k - sk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))

    grid = (bh, nq, nk)
    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        seq_q=sq, seq_k=sk, causal=causal, window=window, q_offset=q_offset,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda b, i, j, g=q_heads_per_kv: (b // g, j, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda b, i, j, g=q_heads_per_kv: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, nq * block_q, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq]
