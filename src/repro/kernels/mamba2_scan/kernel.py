"""Chunked SSD (Mamba-2) scan as a Pallas TPU kernel.

Grid = (B, H, n_chunks); the chunk dimension is sequential ("arbitrary"),
carrying the per-(batch, head) SSM state [P, N] in fp32 VMEM scratch.
Per chunk the kernel does the Mamba-2 §6 block decomposition:

  y_intra = (tril(C Bᵀ ⊙ exp(lᵢ−lⱼ))) · XW          (quadratic in Q only)
  y_inter = exp(l) ⊙ (C · Sᵀ)
  S'      = exp(l_Q)·S + Σⱼ exp(l_Q−lⱼ)·XWⱼ ⊗ Bⱼ

Inputs are pre-weighted outside the kernel (xw = x·dt, dta = dt·A): the
elementwise prologue fuses into the surrounding XLA graph, the kernel owns
the scan structure.  VMEM per step ≈ Q·(P+2N+Q)·4B ≈ 0.25 MB at
Q=128, P=N=64 — MXU dims are multiples of 64; Q is the 128-aligned axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(xw_ref, dta_ref, b_ref, c_ref, y_ref, sfin_ref, s_ref, *,
            chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def init():
        s_ref[...] = jnp.zeros_like(s_ref)

    xw = xw_ref[0, 0].astype(jnp.float32)        # [Q, P]
    dta = dta_ref[0, 0].astype(jnp.float32)      # [Q]
    b = b_ref[0].astype(jnp.float32)             # [Q, N]
    c = c_ref[0].astype(jnp.float32)             # [Q, N]

    l = jnp.cumsum(dta)                          # [Q]
    # intra-chunk: M[i,j] = (c_i·b_j)·exp(l_i−l_j) for j ≤ i
    g = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Q,Q]
    ldiff = l[:, None] - l[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    m = jnp.where(jj <= ii, g * jnp.exp(ldiff), 0.0)
    y = jax.lax.dot(m, xw, preferred_element_type=jnp.float32)

    # inter-chunk: exp(l_i) · (c_i · Sᵀ)
    s = s_ref[...]                               # [P, N]
    y = y + jnp.exp(l)[:, None] * jax.lax.dot_general(
        c, s, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)      # [Q, P]
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: S' = exp(l_Q)·S + Σ_j exp(l_Q−l_j)·xw_j ⊗ b_j
    decay_end = jnp.exp(l[chunk - 1] - l)        # [Q]
    s_new = s * jnp.exp(l[chunk - 1]) + jax.lax.dot_general(
        xw * decay_end[:, None], b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # [P, N]
    s_ref[...] = s_new

    @pl.when(ci == n_chunks - 1)
    def finish():
        sfin_ref[0, 0] = s_new


def ssd_scan_kernel(xw: jax.Array, dta: jax.Array, b: jax.Array,
                    c: jax.Array, *, chunk: int = 128,
                    interpret: bool = False):
    """xw: [B, H, S, P]; dta: [B, H, S]; b/c: [B, S, N].
    Returns (y [B, H, S, P], s_final [B, H, P, N])."""
    bsz, h, s, p = xw.shape
    n = b.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    def xw_map(bi, hi, ci):
        return (bi, hi, ci, 0)

    def dta_map(bi, hi, ci):
        return (bi, hi, ci)

    def bc_map(bi, hi, ci):
        return (bi, ci, 0)

    def sfin_map(bi, hi, ci):
        return (bi, hi, 0, 0)

    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=nc)
    y, s_final = pl.pallas_call(
        kernel,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), xw_map),
            pl.BlockSpec((1, 1, chunk), dta_map),
            pl.BlockSpec((1, chunk, n), bc_map),
            pl.BlockSpec((1, chunk, n), bc_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), xw_map),
            pl.BlockSpec((1, 1, p, n), sfin_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s, p), xw.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xw, dta, b, c)
    return y, s_final
