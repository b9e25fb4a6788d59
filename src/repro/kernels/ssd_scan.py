"""Mamba2 SSD chunk-scan Pallas TPU kernel.

Grid: (batch×heads, chunks); the chunk axis is ``arbitrary`` (sequential)
and carries the (N, P) recurrent state in VMEM scratch — the TPU-native
mapping of the SSD inter-chunk recurrence. Per grid cell the kernel does
three small MXU matmuls (C·Bᵀ, (L∘scores)·X, Bᵀ·X) over a (Q, ·) chunk
tile, with Q chosen 128 to align the systolic array.

Inputs are per-head (groups pre-broadcast by the wrapper):
  x (BH, S, P), dt (BH, S), B/C (BH, S, N), A (BH,)
Outputs: y (BH, S, P) and the final state (BH, N, P).

Oracle: ``repro.kernels.ref.ssd_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(
    x_ref, dt_ref, b_ref, c_ref, a_ref,
    y_ref, state_out_ref,
    state_scr,                       # (N, P) f32 scratch
    *,
    chunk: int,
):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)        # (Q,)
    bm = b_ref[0].astype(jnp.float32)         # (Q, N)
    cm = c_ref[0].astype(jnp.float32)         # (Q, N)
    a = a_ref[0].astype(jnp.float32)          # scalar (negative)

    dA = dt * a                               # (Q,)
    cum = jnp.cumsum(dA)                      # (Q,)
    total = cum[-1]

    # intra-chunk: L[i,j] = exp(cum_i - cum_j) for j <= i
    diff = cum[:, None] - cum[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(tri, jnp.exp(diff), 0.0)
    scores = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )                                          # (Q, Q)
    w = scores * L * dt[None, :]
    y = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )                                          # (Q, P)

    # carried state: y += exp(cum) * (C @ state)
    state = state_scr[...]                     # (N, P)
    y_inter = jax.lax.dot_general(
        cm, state, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    y = y + y_inter * jnp.exp(cum)[:, None]

    # state update: state' = exp(total)*state + B^T @ (decay_to_end*dt*x)
    decay = jnp.exp(total - cum) * dt          # (Q,)
    xw = x * decay[:, None]
    chunk_state = jax.lax.dot_general(
        bm, xw, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )                                          # (N, P)
    new_state = chunk_state + jnp.exp(total) * state
    state_scr[...] = new_state

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _emit_state():
        state_out_ref[0] = new_state.astype(state_out_ref.dtype)


def ssd_scan(
    x: jnp.ndarray,                  # (BH, S, P)
    dt: jnp.ndarray,                 # (BH, S) — post-softplus
    A: jnp.ndarray,                  # (BH,) negative decay per head
    Bm: jnp.ndarray,                 # (BH, S, N)
    Cm: jnp.ndarray,                 # (BH, S, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
):
    """Returns (y (BH, S, P), final_state (BH, N, P))."""
    bh, s, p = x.shape
    n = Bm.shape[-1]
    assert s % chunk == 0, f"seq {s} % chunk {chunk}"
    nc = s // chunk
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, state = pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk), lambda b, c: (b, c)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1,), lambda b, c: (b,)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, n, p), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), x.dtype),
            jax.ShapeDtypeStruct((bh, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, dt, Bm, Cm, A)
    return y, state
