"""Paged decode attention as a Pallas TPU kernel.

This is WTF's slice-pointer indirection turned into a kernel input format:
the page table (= the metadata list) is SCALAR-PREFETCHED, and the kernel
dereferences it itself to copy exactly the pages a sequence references,
in table order, out of the pools in HBM, without ever materializing the
gathered K/V.  Streaming softmax state (acc/m/l) persists in VMEM scratch
across a sequence's blocks.

Pools: the kernel reads every layer's pages where the serving engine
keeps them, in one stacked pool a side, `[L, Hkv, P, T / f, f·D]`
(`pool_shape`), and takes the layer as a scalar-prefetched operand: its
DMAs copy `pool.at[layer, :, page]`.  So a decode step hands it the pools
it updates in place, and no layer's pool is sliced out or relaid.
One layer's `[Hkv, P, T, D]` pages are read as a stack of one, folded
(`fold_pages`), at layer 0 (`ops.paged_attention`).

Tiling: grid = (B, ceil(pages_per_seq / pages_per_block)).  A grid step
takes one block of `pages_per_block` consecutive table entries of one
sequence, for every KV head: one DMA a page for K and one for V, each
copying that page of all Hkv heads (Hkv·T·D elements; for SmolLM's
decode 16 + 16 DMAs of 5·16·64 bfloat16, 320 KB a step) into a
double-buffered VMEM block.  While a block is computed, the next live
block is already in flight, also where it belongs to the next sequence.
A step whose block starts at or past the sequence's length issues no DMA
and no compute, so a short sequence in a batch with a long table costs
one empty grid step per dead block; the last live block masks positions
past the length.

`pages_per_block` is a function of shapes (`pages_per_block` below): a
block of about `BLOCK_TOKENS` tokens a head, fewer where the double-
buffered K and V blocks would pass `BLOCK_VMEM_BYTES`, and at most the
table's width.

Lanes: a head dim under 128 (64 for SmolLM) would fill half of every
(8, 128) tile of the pools, and the chip's DMA cannot slice such a page.
So the pools fold `f = 128 // D` consecutive tokens into one row of 128
lanes (a page [T, D] is stored as [T / f, 128]: token `r·f + c` in row
r, lanes [cD, (c+1)D)), and the kernel computes on rows: query pane c
holds the queries in lanes [cD, (c+1)D) and zeros elsewhere, so its
scores are those of the rows' c-th tokens, and its share of p·V lands in
the same lanes, which the wrapper sums.  Where D is 128, or a page's
T·D is no whole number of rows, f is 1 and a row is a token.
Scores, softmax state and p·V are float32 at HIGHEST precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
BLOCK_TOKENS = 256               # tokens a grid step streams for each head
BLOCK_VMEM_BYTES = 8 << 20       # the double-buffered K and V blocks


def pages_per_block(page_tokens: int, kv_heads: int, head_dim: int,
                    itemsize: int, table_width: int) -> int:
    """Pages of one sequence a grid step copies and computes."""
    page_bytes = 2 * 2 * kv_heads * page_tokens * head_dim * itemsize
    ppb = min(max(1, BLOCK_TOKENS // page_tokens),
              max(1, BLOCK_VMEM_BYTES // page_bytes))
    return max(1, min(ppb, table_width))


def _fold(page_tokens: int, head_dim: int) -> int:
    """Tokens a row of the folded pages holds (1: no folding)."""
    if head_dim < LANES and LANES % head_dim == 0 \
            and (page_tokens * head_dim) % LANES == 0:
        return LANES // head_dim
    return 1


def pool_shape(layers: int, kv_heads: int, pages: int, page_tokens: int,
               head_dim: int) -> tuple:
    """The stacked pool's layout the kernel reads: [L, Hkv, P, T/f, f·D]."""
    f = _fold(page_tokens, head_dim)
    return (layers, kv_heads, pages, page_tokens // f, f * head_dim)


def fold_pages(pages: jax.Array) -> jax.Array:
    """[..., T, D] pages as the pool stores them, [..., T/f, f·D]."""
    t, d = pages.shape[-2:]
    f = _fold(t, d)
    return pages.reshape(*pages.shape[:-2], t // f, f * d)


def _kernel(table_ref, lens_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, slot_ref, acc_ref, m_ref, l_ref, *,
            ppb: int, blocks: int, batch: int, fold: int, scale: float):
    b = pl.program_id(0)
    i = pl.program_id(1)
    _, kv_heads, _, page_rows, lanes = k_hbm.shape
    rows = ppb * page_rows                     # rows of one head's block
    block_tokens = rows * fold
    panes = m_ref.shape[1]                     # query rows of one pane
    length = lens_ref[b]
    layer = layer_ref[0]

    def copies(seq, blk, slot):
        """The block's DMAs: page j of K and V, all heads, into slot."""
        out = []
        for j in range(ppb):
            page = table_ref[seq * blocks * ppb + blk * ppb + j]
            for hbm, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                out.append(pltpu.make_async_copy(
                    hbm.at[layer, :, page], buf.at[slot, :, j],
                    sems.at[slot]))
        return out

    @pl.when((b == 0) & (i == 0))
    def first():
        slot_ref[0] = 0
        for c in copies(0, 0, 0):
            c.start()

    # block 0 of every sequence is live, so the prefetch chain never
    # starts a copy that no step waits for
    @pl.when((i == 0) | (i * block_tokens < length))
    def step():
        slot = slot_ref[0]
        last = (i + 1) * block_tokens >= length
        nxt_b = jnp.where(last, b + 1, b)
        nxt_i = jnp.where(last, 0, i + 1)

        @pl.when(nxt_b < batch)
        def prefetch():
            for c in copies(nxt_b, nxt_i, 1 - slot):
                c.start()
            slot_ref[0] = 1 - slot

        @pl.when(i == 0)
        def init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        for c in copies(b, i, slot):
            c.wait()

        # token of pane c, row r: i * block_tokens + r * fold + c
        col = jax.lax.broadcasted_iota(jnp.int32, (panes, rows), 1)
        base = i * block_tokens + col * fold
        # pane c's share of p·V belongs to the lanes of the c-th tokens
        lane = jax.lax.broadcasted_iota(jnp.int32, (panes, lanes), 1)
        width = lanes // fold
        segs = [(lane >= c * width) & (lane < (c + 1) * width)
                for c in range(fold)]
        for h in range(kv_heads):
            q = q_ref[0, h]                                 # [f*G', 128]
            k = k_buf[slot, h].astype(jnp.float32).reshape(rows, lanes)
            v = v_buf[slot, h].astype(jnp.float32).reshape(rows, lanes)
            # HIGHEST: without it the MXU rounds f32 operands to bf16 (seen
            # as 7e-3 abs error against the f32 reference on a v5e)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST) * scale  # [f*G', R]
            s = [jnp.where(base + c < length,
                           s[c * panes:(c + 1) * panes], NEG_INF)
                 for c in range(fold)]

            m_prev = m_ref[h]                                   # [G', 1]
            m_new = m_prev
            for sc in s:
                m_new = jnp.maximum(m_new, jnp.max(sc, axis=1, keepdims=True))
            p = [jnp.exp(sc - m_new) for sc in s]
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_ref[h] * alpha
            for pc in p:
                l_new = l_new + jnp.sum(pc, axis=1, keepdims=True)
            pv = jax.lax.dot(
                jnp.concatenate(p, axis=0) if fold > 1 else p[0], v,
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)          # [f*G', 128]
            acc = acc_ref[h] * alpha
            for c, seg in enumerate(segs):
                acc = acc + jnp.where(seg, pv[c * panes:(c + 1) * panes], 0.0)
            acc_ref[h] = acc
            l_ref[h] = l_new
            m_ref[h] = m_new

    @pl.when(i == blocks - 1)
    def finish():
        for h in range(kv_heads):
            out = acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
            # an empty sequence attends to nothing (its block 0 ran masked)
            o_ref[0, h] = jnp.where(length > 0, out, 0.0)


def paged_attention_kernel(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, page_table: jax.Array,
                           lengths: jax.Array, layer: jax.Array, *,
                           interpret: bool = False) -> jax.Array:
    """q: [B, H, D]; k_pool/v_pool: every layer's pages, [L, Hkv, P, T/f,
    f·D] (`pool_shape`); page_table: [B, PP] int32 (-1 = unused);
    lengths: [B]; layer: the pools' layer to read (an int32 scalar).
    Returns [B, H, D]."""
    b, h, d = q.shape
    _, hkv, _, rows, lanes = k_pool.shape
    fold = lanes // d
    t = rows * fold
    groups = h // hkv
    pp = page_table.shape[1]
    ppb = pages_per_block(t, hkv, d, k_pool.dtype.itemsize, pp)
    blocks = -(-pp // ppb)
    panes = -(-groups // 8) * 8                # query rows, whole sublanes

    # query pane c: q in lanes [c*D, (c+1)*D), zeros elsewhere
    qg = jnp.pad(q.reshape(b, hkv, groups, d).astype(jnp.float32),
                 ((0, 0), (0, 0), (0, panes - groups), (0, 0)))
    qf = (qg[:, :, None, :, None, :]
          * jnp.eye(fold, dtype=jnp.float32)[:, None, :, None]
          ).reshape(b, hkv, fold * panes, lanes)
    # unused entries and the padding up to whole blocks read page 0, and
    # every position they hold lies past the length
    table = jnp.maximum(page_table, 0).astype(jnp.int32)
    table = jnp.pad(table, ((0, 0), (0, blocks * ppb - pp))).reshape(-1)

    def q_map(bi, i, tbl, lens, lyr):
        return (bi, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, blocks),
        in_specs=[
            pl.BlockSpec((1, hkv, fold * panes, lanes), q_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hkv, panes, lanes), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, hkv, ppb, rows, lanes), k_pool.dtype),
            pltpu.VMEM((2, hkv, ppb, rows, lanes), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((hkv, panes, lanes), jnp.float32),
            pltpu.VMEM((hkv, panes, 1), jnp.float32),
            pltpu.VMEM((hkv, panes, 1), jnp.float32),
        ],
    )

    kernel = functools.partial(_kernel, ppb=ppb, blocks=blocks, batch=b,
                               fold=fold, scale=1.0 / np.sqrt(d))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, panes, lanes), jnp.float32),
        # the prefetch chain runs across sequences: the grid is sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(table, lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qf, k_pool, v_pool)
    out = out[:, :, :groups].reshape(b, hkv, groups, fold, d).sum(axis=3)
    return out.astype(q.dtype).reshape(b, h, d)
