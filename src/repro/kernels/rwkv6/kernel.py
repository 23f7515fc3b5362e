"""RWKV6 (Finch) recurrence — Pallas TPU kernel.

Chunked linear-recurrence decomposition (the TPU adaptation of the
CUDA wkv6 kernel): the sequence is split into chunks of C tokens;
the (hd × hd) per-head state is carried across chunks in VMEM scratch
(TPU grids execute the last dimension sequentially), and within a chunk
the pairwise-decay interaction is a *dense triangular GEMM* in the
factorized form

    A[t, s] = (r_t · e^{cum_ex_t}) · (k_s · e^{-cum_s}),  s < t

so the MXU does the O(C²·hd) work instead of a scalar recurrence —
plus the diagonal bonus-u term and the inter-chunk term r̂ @ S.

  grid = (B, H, S/C);  blocks: r/k/v/w tiles (C × hd) in VMEM, the
  head's bonus row u (1 × hd), state scratch (hd × hd) fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_ref, *,
                  chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0, 0].astype(jnp.float32)               # (C, hd)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)                  # (1, hd)

    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    logw = jnp.log(w)
    # inclusive prefix sum over the chunk as a lower-triangular GEMM
    # (Mosaic has no cumsum); HIGHEST keeps the f32 sum exact enough
    # for the decay exponents below
    cum = jax.lax.dot_general(
        (si <= ti).astype(jnp.float32), logw, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)           # (C, hd)
    cum_ex = cum - logw
    r_hat = r * jnp.exp(cum_ex)
    k_hat = k * jnp.exp(-cum)

    # the scratch holds the state transposed, Sᵀ (hd_v × hd_k), so that
    # neither the read nor the update needs a transpose or a row index
    St = s_ref[...]
    y_inter = jax.lax.dot_general(
        r_hat, St, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)           # (C, hd)

    att = jax.lax.dot_general(
        r_hat, k_hat, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)           # (C, C)
    att = jnp.where(si < ti, att, 0.0)                # strict lower triangle
    y_intra = jax.lax.dot_general(
        att, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True)  # (C, 1)
    y_ref[0, 0] = (y_inter + y_intra + diag * v).astype(y_ref.dtype)

    # state update: S_out = e^{cum[-1]} ⊙ S_in + Σ_s (k_s e^{tail_s}) v_sᵀ
    total = jnp.sum(logw, axis=0, keepdims=True)      # (1, hd) = cum[-1]
    k_tail = k * jnp.exp(total - cum)                 # (C, hd)
    s_ref[...] = jnp.exp(total) * St + jax.lax.dot_general(
        v, k_tail, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def rwkv6_kernel(r, k, v, w, u, *, chunk: int = 64,
                 interpret: bool = False) -> jax.Array:
    """r,k,v,w: (B, H, S, hd); u: (H, hd). S % chunk == 0 (ops pads).
    Returns y: (B, H, S, hd)."""
    B, H, S, hd = r.shape
    chunk = min(chunk, S)
    nc = S // chunk

    kernel = functools.partial(_rwkv6_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0)),
            # u as (H, 1, hd): the block's last two dims then equal the
            # array's, which Mosaic requires of blocks not (8, 128)-aligned
            pl.BlockSpec((1, 1, hd), lambda b, h, c: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, hd),
                               lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), r.dtype),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u.reshape(H, 1, hd))
