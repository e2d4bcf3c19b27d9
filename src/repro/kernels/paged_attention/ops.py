"""jit'd wrapper over one layer's pages, [Hkv, P, T, D]: the kernel on
them as a stack of one layer, or the reference.  The serving engine
calls `paged_attention_kernel` itself, on its stacked pools and a
layer."""
from __future__ import annotations

import functools

import jax

from .kernel import fold_pages, paged_attention_kernel
from .ref import paged_attention_ref


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def paged_attention(q, k_pages, v_pages, page_table, lengths,
                    *, interpret: bool = False, use_kernel: bool = True):
    """q: [B, H, D]; k_pages/v_pages: [Hkv, P, T, D] (one layer);
    page_table: [B, PP]; lengths: [B] → [B, H, D]."""
    if use_kernel:
        return paged_attention_kernel(
            q, fold_pages(k_pages)[None], fold_pages(v_pages)[None],
            page_table, lengths, 0, interpret=interpret)
    return paged_attention_ref(q, k_pages, v_pages, page_table, lengths)
