"""Continuous-batching serving engine over the paged KV cache.

WTF's design, end to end: the PagedKVCache (metadata manager) plays the
HyperDex role — page tables are metadata lists, pages are slices, prefix
forking is `copy` — while the device pools play the storage servers.  The
decode step consumes the page table DIRECTLY via the Pallas
`paged_attention` kernel; gathered K/V is never materialized.

Dense-family models (smollm / qwen2 / command-r / mistral / llava-text).
Layout: one pool a side on device, [L, Hkv, P, T/f, f·D], the paged
kernel's own (`pool_shape`): a page of a head is T/f rows of f
consecutive tokens in 128 lanes (f = 128 // D where the page folds, else
1).  Prefill writes a prompt's K/V into its pages in one fused step;
decode appends one token per step for the whole batch, updating the
donated pools in place, and the kernel reads each layer where it lies.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention.kernel import (paged_attention_kernel,
                                                  pages_per_block,
                                                  pool_shape)
from repro.models import layers as L
from repro.util import trace
from .kv_cache import CacheConfig, PagedKVCache


@dataclass
class EngineConfig:
    page_tokens: int = 16
    num_pages: int = 2048
    max_seqs: int = 64
    max_tokens: int = 512


@dataclass
class Request:
    seq_id: int
    prompt: np.ndarray
    max_new: int
    out: List[int] = field(default_factory=list)
    done: bool = False


class Engine:
    def __init__(self, model, params, cfg: EngineConfig = EngineConfig()):
        if model.cfg.arch_kind not in ("dense", "vlm"):
            raise ValueError("paged engine supports the dense family")
        self.model = model
        self.mcfg = model.cfg
        self.cfg = cfg
        hd = self.mcfg.head_dim_
        self.cache = PagedKVCache(CacheConfig(
            num_layers=self.mcfg.n_layers,
            num_kv_heads=self.mcfg.n_kv_heads, head_dim=hd,
            page_tokens=cfg.page_tokens, num_pages=cfg.num_pages,
            max_seqs=cfg.max_seqs, dtype="float32"), allocate=False)
        dt = jnp.dtype(self.mcfg.compute_dtype)
        shape = pool_shape(self.mcfg.n_layers, self.mcfg.n_kv_heads,
                           cfg.num_pages, cfg.page_tokens, hd)
        self.k_pool = jnp.zeros(shape, dt)
        self.v_pool = jnp.zeros(shape, dt)
        # reserved scratch page: prefill writes of already-shared prefix
        # positions are redirected here so shared pages stay immutable
        self.scratch_page = self.cache._alloc_page()
        self.params = params
        self._requests: Dict[int, Request] = {}
        self._next_id = 0
        self._prefill = jax.jit(functools.partial(
            _prefill_step, cfg=self.mcfg,
            page_tokens=cfg.page_tokens))
        # the Pallas kernel compiles for the TPU; any other backend runs
        # it in interpret mode
        self.kernel_interpret = jax.default_backend() != "tpu"
        # the step updates the pools in place: they are donated
        self._decode = jax.jit(functools.partial(
            _decode_step, cfg=self.mcfg, page_tokens=cfg.page_tokens,
            interpret=self.kernel_interpret), donate_argnums=(1, 2))

    # ------------------------------------------------------------ requests
    def add(self, prompt: np.ndarray, max_new: int = 16,
            fork_from: Optional[int] = None) -> int:
        """Admit a request.  `fork_from` shares the parent's prefix pages
        (WTF `copy`: refcounted, zero data movement) — only the new suffix
        is prefilled.  The admission is the span ``engine.add``."""
        sid = self._next_id
        self._next_id += 1
        with trace.span("engine.add", sid=sid,
                        prompt_tokens=len(prompt)) as rec:
            shared = 0
            if fork_from is not None:
                with trace.span("engine.fork"):
                    self.cache.fork(fork_from, sid)
                shared = self.cache.seq_len[sid]
                # only positions past the shared prefix need prefill
                assert len(prompt) >= shared, \
                    "fork prefix longer than prompt"
                if shared % self.cfg.page_tokens:
                    # shared prefix ends mid-page: COW the open page so
                    # the suffix prefill cannot touch the parent's copy
                    self._cow_page(sid, shared // self.cfg.page_tokens)
            else:
                self.cache.create(sid)
            rec.attrs["shared_tokens"] = shared
            req = Request(sid, prompt, max_new)
            if len(prompt) > shared:
                # the prefill's last-position logits ARE the first output
                # token
                req.out.append(self._run_prefill(sid, prompt, shared))
                req.done = len(req.out) >= max_new
            self._requests[sid] = req
        return sid

    def _cow_page(self, sid: int, page_idx: int) -> None:
        """Give ``sid`` its own copy of its page ``page_idx`` where that
        page is shared (span ``engine.cow``)."""
        tbl = self.cache.page_table[sid]
        pid = tbl[page_idx]
        if self.cache.refcount[pid] <= 1:
            return
        with trace.span("engine.cow"):
            new = self.cache._alloc_page()
            self.k_pool = self.k_pool.at[:, :, new].set(
                self.k_pool[:, :, pid])
            self.v_pool = self.v_pool.at[:, :, new].set(
                self.v_pool[:, :, pid])
            self.cache._release_page(pid)
            tbl[page_idx] = new
            self.cache.stats["pages_copied"] += 1

    def _ensure_pages(self, sid: int, upto: int) -> None:
        t = self.cfg.page_tokens
        table = self.cache.page_table[sid]
        while len(table) * t < upto:
            table.append(self.cache._alloc_page())

    def _run_prefill(self, sid: int, prompt: np.ndarray,
                     start: int) -> int:
        n = len(prompt)
        self._ensure_pages(sid, n)
        table = np.asarray(self.cache.page_table[sid], np.int32)
        next_tok, kp, vp = self._prefill(
            self.params, self.k_pool, self.v_pool,
            jnp.asarray(prompt[None, :], jnp.int32),
            jnp.asarray(table[None, :]),
            jnp.asarray(start, jnp.int32),
            jnp.asarray(self.scratch_page, jnp.int32))
        self.k_pool, self.v_pool = kp, vp
        self.cache.seq_len[sid] = n
        with trace.span("engine.prefill.wait"):
            return int(next_tok[0])

    # --------------------------------------------------------------- step
    def step(self) -> List[int]:
        """One decode step for every active sequence; returns finished ids.
        The step is the span ``engine.step``, whose ``batch`` is 0 where
        no sequence was active; else it carries the page table's width,
        the paged kernel's live and spanned blocks (``kv_blocks``,
        ``kv_blocks_grid``), and ``pools_donated``: 1 where the step
        consumed the pools it was given in place, else 0."""
        with trace.span("engine.step") as rec:
            active = [r for r in self._requests.values() if not r.done]
            rec.attrs["batch"] = len(active)
            return self._step(active, rec) if active else []

    def _step(self, active: List[Request], rec: trace.Record) -> List[int]:
        with trace.span("engine.step.prepare"):
            b = len(active)
            t = self.cfg.page_tokens
            tokens = np.zeros((b, 1), np.int32)
            pos = np.zeros((b,), np.int32)
            for i, r in enumerate(active):
                tokens[i, 0] = r.out[-1]       # prefill seeded out[0]
                pos[i] = self.cache.seq_len[r.seq_id]
                self._ensure_pages(r.seq_id, int(pos[i]) + 1)
                # COW before writing into a shared open page
                self._cow_page(r.seq_id, int(pos[i]) // t)
            tbl_arr, _ = self.cache.table_array([r.seq_id for r in active])
        # the paged kernel's blocks, one a KV head: those it walks (each
        # sequence's up to its length after this step's token) and those
        # its grid spans
        width = tbl_arr.shape[1]
        hkv = self.mcfg.n_kv_heads
        ppb = pages_per_block(t, hkv, self.mcfg.head_dim_,
                              self.k_pool.dtype.itemsize, width)
        rec.attrs["table_width"] = width
        rec.attrs["kv_blocks"] = hkv * int(np.sum(-(-(pos + 1) // (ppb * t))))
        rec.attrs["kv_blocks_grid"] = hkv * b * -(-width // ppb)
        pools_in = (self.k_pool, self.v_pool)
        next_tok, self.k_pool, self.v_pool = self._decode(
            self.params, self.k_pool, self.v_pool,
            jnp.asarray(tokens), jnp.asarray(pos),
            jnp.asarray(np.maximum(tbl_arr, 0)))
        rec.attrs["pools_donated"] = int(all(p.is_deleted()
                                             for p in pools_in))
        with trace.span("engine.step.wait"):
            next_tok = np.asarray(next_tok)
        finished = []
        for i, r in enumerate(active):
            self.cache.seq_len[r.seq_id] += 1
            r.out.append(int(next_tok[i]))
            if len(r.out) >= r.max_new:
                r.done = True
                finished.append(r.seq_id)
        return finished

    def result(self, sid: int) -> List[int]:
        return self._requests[sid].out

    def release(self, sid: int) -> None:
        self.cache.release(sid)
        self._requests.pop(sid, None)


# ---------------------------------------------------------------- compute
def _qkv(p, y, cfg, pos):
    dt = jnp.dtype(cfg.compute_dtype)
    hd = cfg.head_dim_

    def proj(name, heads):
        out = jnp.einsum("bsd,dhk->bshk", y,
                         L.cast(p[name], dt).reshape(cfg.d_model, heads,
                                                     hd))
        if cfg.qkv_bias and f"{name}_b" in p:
            out = out + L.cast(p[f"{name}_b"], dt).reshape(1, 1, heads, hd)
        return out

    q = L.apply_rope(proj("wq", cfg.n_heads), pos, cfg.rope_theta)
    k = L.apply_rope(proj("wk", cfg.n_kv_heads), pos, cfg.rope_theta)
    v = proj("wv", cfg.n_kv_heads)
    return q, k, v


def _write_tokens(pool, vals, layer, pages, slots):
    """Write one token a sequence, vals [B, Hkv, D], into pool [L, Hkv,
    P, T/f, f·D] at `layer`: sequence b's page `pages[b]` of every head
    is read, takes the token in row slots[b] // f, lanes from
    (slots[b] % f)·D, and is written back whole.  The sequences' pages
    are distinct.  (Windows of whole pages keep the scatter one fusion,
    in place and in the pool's layout.)"""
    _, hkv, d = vals.shape
    rows, lanes = pool.shape[-2:]
    f = lanes // d
    at = (layer, jnp.arange(hkv)[None, :], pages[:, None])
    row = jnp.arange(rows)[:, None]
    lane = jnp.arange(lanes)[None, :]
    hit = ((row == (slots // f)[:, None, None, None])
           & (lane // d == (slots % f)[:, None, None, None]))
    new = jnp.tile(vals.astype(pool.dtype), (1, 1, f))[:, :, None, :]
    return pool.at[at].set(jnp.where(hit, new, pool[at]))


def _write_prompt(pool, vals, table, start, scratch_page, page_tokens):
    """Write a prompt's vals [L, S, Hkv, D] into pool at the pages of
    `table` [PP], whole pages at a time: a page takes positions in
    [start, S) and keeps the rest as they were.  Positions < `start`
    belong to a shared (immutable) prefix: a page wholly below it is
    written to the reserved scratch page instead."""
    n_layers, s, hkv, d = vals.shape
    rows, lanes = pool.shape[-2:]
    pp, t = table.shape[0], page_tokens
    new = jnp.pad(vals.astype(pool.dtype),
                  ((0, 0), (0, pp * t - s), (0, 0), (0, 0)))
    new = new.reshape(n_layers, pp, t, hkv, d).transpose(0, 3, 1, 2, 4)
    new = new.reshape(n_layers, hkv, pp, rows, lanes)
    # the position each element of the prompt's pages holds
    pos = (jnp.arange(pp)[:, None, None] * t
           + jnp.arange(rows)[None, :, None] * (lanes // d)
           + jnp.arange(lanes)[None, None, :] // d)       # [PP, R, lanes]
    li = jnp.arange(n_layers)[:, None, None]
    h = jnp.arange(hkv)[None, :, None]
    merged = jnp.where((pos >= start) & (pos < s), new,
                       pool[li, h, table[None, None, :]])
    dest = jnp.where((jnp.arange(pp) + 1) * t <= start, scratch_page, table)
    return pool.at[li, h, dest[None, None, :]].set(merged)


def _prefill_step(params, k_pool, v_pool, tokens, table, start,
                  scratch_page, *, cfg, page_tokens):
    """Full-prompt forward: writes K/V pages, returns updated pools.
    tokens/table: [1, S] / [1, PP].  Positions < `start` belong to a
    shared (immutable) prefix — their writes are redirected to the
    reserved scratch page."""
    x = L.embed(params, tokens, cfg, None)
    s = tokens.shape[1]
    pos = jnp.arange(s)[None, :]

    def body(x, p):
        y = L.rms_norm(x, p["ln"], cfg.norm_eps)
        q, k, v = _qkv(p, y, cfg, pos)
        attn = L.attention(q, k, v, causal=True,
                           sliding_window=cfg.sliding_window)
        dt = jnp.dtype(cfg.compute_dtype)
        o = jnp.einsum("bshk,hkd->bsd", attn,
                       L.cast(p["wo"], dt).reshape(cfg.n_heads,
                                                   cfg.head_dim_,
                                                   cfg.d_model))
        x = x + o
        ln2 = p["ln2"] if "ln2" in p else p["ln"]
        x = x + L.swiglu({**p, "ln": ln2}, x, cfg)
        return x, (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = L.unembed(params, x[:, -1:], cfg, None)
    next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    k_pool = _write_prompt(k_pool, ks[:, 0], table[0], start, scratch_page,
                           page_tokens)
    v_pool = _write_prompt(v_pool, vs[:, 0], table[0], start, scratch_page,
                           page_tokens)
    return next_tok, k_pool, v_pool


def _decode_step(params, k_pool, v_pool, tokens, pos, table, *,
                 cfg, page_tokens, interpret):
    """One token for B sequences against the paged cache.  The scan
    carries the pools: each layer scatters its B new K/V rows into them,
    and the kernel reads that layer of them, so where the caller donates
    the pools nothing copies them."""
    x = L.embed(params, tokens, cfg, None)
    lengths = pos + 1
    # this token's page and slot, for every layer
    pages = jnp.take_along_axis(table, (pos // page_tokens)[:, None],
                                axis=1)[:, 0]
    slots = pos % page_tokens

    def body(carry, inp):
        x, k_pool, v_pool = carry
        p, li = inp
        y = L.rms_norm(x, p["ln"], cfg.norm_eps)
        q, k, v = _qkv(p, y, cfg, pos[:, None])
        k_pool = _write_tokens(k_pool, k[:, 0], li, pages, slots)
        v_pool = _write_tokens(v_pool, v[:, 0], li, pages, slots)
        attn = paged_attention_kernel(
            q[:, 0], k_pool, v_pool, table, lengths, li,
            interpret=interpret)[:, None]
        dt = jnp.dtype(cfg.compute_dtype)
        o = jnp.einsum("bshk,hkd->bsd", attn.astype(dt),
                       L.cast(p["wo"], dt).reshape(cfg.n_heads,
                                                   cfg.head_dim_,
                                                   cfg.d_model))
        x = x + o
        ln2 = p["ln2"] if "ln2" in p else p["ln"]
        x = x + L.swiglu({**p, "ln": ln2}, x, cfg)
        return (x, k_pool, v_pool), None

    li = jnp.arange(cfg.n_layers)
    (x, k_pool, v_pool), _ = jax.lax.scan(
        body, (x, k_pool, v_pool), (params["layers"], li))
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = L.unembed(params, x, cfg, None)
    return (jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32),
            k_pool, v_pool)
