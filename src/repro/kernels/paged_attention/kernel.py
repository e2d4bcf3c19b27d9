"""Paged decode attention as a Pallas TPU kernel.

This is WTF's slice-pointer indirection turned into a kernel input format:
the page table (= the metadata list) is SCALAR-PREFETCHED, and the K/V
page index maps dereference it directly —

    index_map(b, hkv, i, table, lens) -> (hkv, table[b, i], 0, 0)

so the kernel streams exactly the pages a sequence references, in table
order, without ever materializing the gathered K/V.  Streaming softmax
state (acc/m/l) persists in VMEM scratch across the page grid dimension.

Tiling: grid = (B, Hkv, pages_per_seq); per-step VMEM = one K page + one
V page (T·D each) + the q head-group pane (G·D) ≈ tens of kB.  Pages past
a sequence's length are skipped with `pl.when` (no wasted bandwidth on
short sequences — the table walk stops where the metadata ends).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(table_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, page_tokens: int, pages: int,
            scale: float):
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = lens_ref[b]
    start = i * page_tokens

    @pl.when(start < length)
    def compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)          # [T, D]
        v = v_ref[0, 0].astype(jnp.float32)          # [T, D]
        # HIGHEST: without it the MXU rounds f32 operands to bf16 (seen
        # as 7e-3 abs error against the f32 reference on a v5e); decode
        # attention is bound by the page reads, not by these passes
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST) * scale  # [G, T]
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
        m_ref[...] = m_new

    @pl.when(i == pages - 1)
    def finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_attention_kernel(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_table: jax.Array,
                           lengths: jax.Array, *,
                           interpret: bool = False) -> jax.Array:
    """q: [B, H, D]; k_pages/v_pages: [Hkv, P, T, D];
    page_table: [B, PP] int32 (-1 = unused); lengths: [B].
    Returns [B, H, D]."""
    b, h, d = q.shape
    hkv, _, t, _ = k_pages.shape
    groups = h // hkv
    pp = page_table.shape[1]

    qg = q.reshape(b, hkv, groups, d)
    table = jnp.maximum(page_table, 0).astype(jnp.int32)

    def q_map(bi, hi, i, tbl, lens):
        return (bi, hi, 0, 0)

    def kv_map(bi, hi, i, tbl, lens):
        return (hi, tbl[bi, i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, pp),
        in_specs=[
            pl.BlockSpec((1, 1, groups, d), q_map),
            pl.BlockSpec((1, 1, t, d), kv_map),
            pl.BlockSpec((1, 1, t, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, groups, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((groups, d), jnp.float32),
            pltpu.VMEM((groups,), jnp.float32),
            pltpu.VMEM((groups,), jnp.float32),
        ],
    )

    kernel = functools.partial(_kernel, page_tokens=t, pages=pp,
                               scale=1.0 / np.sqrt(d))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, groups, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(table, lengths.astype(jnp.int32), qg, k_pages, v_pages)
    return out.reshape(b, h, d)
