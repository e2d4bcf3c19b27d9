"""Pallas kernel validation: shape/dtype sweeps in interpret mode against
the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.mamba2_scan import ssd_recurrent_ref, ssd_scan
from repro.kernels.paged_attention import paged_attention
from repro.kernels.paged_attention.kernel import (fold_pages,
                                                  paged_attention_kernel)
from repro.kernels.paged_attention.ref import paged_attention_ref

RNG = jax.random.PRNGKey(7)


def _tol(dtype):
    return {"float32": 2e-5, "bfloat16": 2e-2}[jnp.dtype(dtype).name]


# ------------------------------------------------------- flash attention
@pytest.mark.parametrize("b,sq,h,hkv,d", [
    (2, 256, 4, 2, 64),
    (1, 128, 8, 8, 128),
    (2, 512, 4, 1, 64),      # MQA
    (1, 384, 6, 2, 128),     # non-power-of-two seq (3 blocks)
])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_attention_sweep(b, sq, h, hkv, d, dtype):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, sq, h, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, sq, hkv, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, sq, hkv, d)).astype(dtype)
    out = flash_attention(q, k, v, True, None, 128, 128, True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("window", [64, 200])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 64))
    k = jax.random.normal(ks[1], (2, 256, 2, 64))
    v = jax.random.normal(ks[2], (2, 256, 2, 64))
    out = flash_attention(q, k, v, True, window, 128, 64, True)
    ref = attention_ref(q, k, v, causal=True, sliding_window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_grad_matches_ref():
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    v = jax.random.normal(ks[2], (1, 128, 2, 64))

    def f_kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, 64, 64, True))

    def f_ref(q, k, v):
        return jnp.sum(attention_ref(q, k, v, causal=True))

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


# -------------------------------------------------------- paged attention
# SmolLM's decode (Hkv 5, G 3, D 64, T 16) runs blocks of 16 pages, 256
# tokens; explicit lengths place sequence ends against those blocks
@pytest.mark.parametrize("b,h,hkv,d,pages,t,pp,lens", [
    pytest.param(3, 8, 2, 64, 32, 16, 8, None, id="3-8-2-64-32-16-8"),
    pytest.param(2, 4, 4, 128, 16, 32, 4, None, id="2-4-4-128-16-32-4"),
    pytest.param(1, 16, 1, 64, 64, 16, 16, None,   # MQA, long table
                 id="1-16-1-64-64-16-16"),
    # 24 pages: one whole block and one of 8 pages padded to 16
    pytest.param(4, 15, 5, 64, 128, 16, 24, None, id="smollm-width-24"),
    pytest.param(2, 15, 5, 64, 64, 16, 32, [256, 1],
                 id="smollm-one-block-and-one-token"),
    # the short sequence's second and third blocks lie past its end; the
    # long one ends inside its third block
    pytest.param(2, 15, 5, 64, 64, 16, 40, [20, 600],
                 id="smollm-short-and-long"),
    # one block, most of whose table entries are the unused tail (-1)
    pytest.param(1, 15, 5, 64, 32, 16, 16, [37], id="smollm-unused-tail"),
])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_paged_attention_sweep(b, h, hkv, d, pages, t, pp, lens, dtype):
    ks = jax.random.split(RNG, 4)
    q = jax.random.normal(ks[0], (b, h, d)).astype(dtype)
    kp = jax.random.normal(ks[1], (hkv, pages, t, d)).astype(dtype)
    vp = jax.random.normal(ks[2], (hkv, pages, t, d)).astype(dtype)
    lengths, tbl = _page_table(b, pages, t, pp, lens)
    out = paged_attention(q, kp, vp, tbl, lengths, interpret=True)
    ref = paged_attention_ref(q, kp, vp, tbl, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def _page_table(b, pages, t, pp, lens):
    """Lengths (`lens`, else drawn) and a table of distinct pages."""
    rng = np.random.RandomState(0)
    lengths = (rng.randint(1, pp * t + 1, size=b) if lens is None
               else np.array(lens)).astype(np.int32)
    tbl = np.full((b, pp), -1, np.int32)
    free = list(rng.permutation(pages))
    for i, ln in enumerate(lengths):
        for j in range(-(-int(ln) // t)):
            tbl[i, j] = free.pop()
    return jnp.asarray(lengths), jnp.asarray(tbl)


# the serving engine's stacked pools [L, Hkv, P, T/f, f·D]: the kernel
# reads the layer it is given, folded (D 64: two tokens a row) or not
# (D 128), and a sequence of length 0 attends to nothing
@pytest.mark.parametrize("d", [64, 128], ids=["folded", "unfolded"])
@pytest.mark.parametrize("layer", [0, 2, 4], ids=["first", "middle", "last"])
def test_paged_attention_layer_of_stacked_pool(d, layer):
    n_layers, b, h, hkv, pages, t, pp = 5, 3, 6, 2, 24, 16, 6
    ks = jax.random.split(jax.random.fold_in(RNG, d), 3)
    q = jax.random.normal(ks[0], (b, h, d), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (n_layers, hkv, pages, t, d), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (n_layers, hkv, pages, t, d), jnp.bfloat16)
    lengths, tbl = _page_table(b, pages, t, pp, [37, 0, pp * t])
    out = paged_attention_kernel(q, fold_pages(kp), fold_pages(vp), tbl,
                                 lengths, jnp.int32(layer), interpret=True)
    ref = paged_attention_ref(q, kp[layer], vp[layer], tbl, lengths)
    # the reference's softmax over no position is NaN; the kernel gives 0
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(ref, np.float32)[live],
                               atol=_tol("bfloat16"), rtol=_tol("bfloat16"))
    assert not np.asarray(out, np.float32)[~live].any()


# ------------------------------------------------------------- SSD scan
@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 256, 4, 64, 32, 64),
    (1, 128, 2, 64, 64, 128),
    (2, 512, 8, 32, 16, 64),
])
def test_ssd_scan_sweep(b, s, h, p, n, chunk):
    ks = jax.random.split(RNG, 5)
    xh = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a_log = jax.random.normal(ks[2], (h,)) * 0.5
    bb = jax.random.normal(ks[3], (b, s, n))
    cc = jax.random.normal(ks[4], (b, s, n))
    d_skip = jnp.ones((h,))
    y, sf = ssd_scan(xh, dt, a_log, bb, cc, d_skip, chunk=chunk,
                     interpret=True)
    yr, sr = ssd_recurrent_ref(xh * dt[..., None],
                               dt * -jnp.exp(a_log), bb, cc)
    yr = yr + d_skip[None, None, :, None] * xh
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sr),
                               atol=2e-3, rtol=2e-3)


# -------------------------------------------- model-internal chunked paths
def test_model_ssd_chunked_matches_recurrent():
    from repro.models.mamba2 import ssd_chunked
    ks = jax.random.split(RNG, 5)
    b, s, h, p, n = 2, 192, 2, 32, 16
    xh = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a_log = jax.random.normal(ks[2], (h,)) * 0.5
    bb = jax.random.normal(ks[3], (b, s, n))
    cc = jax.random.normal(ks[4], (b, s, n))
    y, sfin = ssd_chunked(xh, dt, a_log, bb, cc, jnp.zeros((h,)), 64)
    yr, sr = ssd_recurrent_ref(xh * dt[..., None],
                               dt * -jnp.exp(a_log), bb, cc)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(sfin), np.asarray(sr),
                               atol=2e-3, rtol=2e-3)


def test_mlstm_chunked_matches_recurrent():
    """The xLSTM chunkwise form vs explicit token-by-token recurrence."""
    from repro.models.xlstm import mlstm_chunked, mlstm_step
    ks = jax.random.split(RNG, 5)
    b, s, h, dk, dv = 2, 128, 2, 16, 32
    q = jax.random.normal(ks[0], (b, s, h, dk))
    k = jax.random.normal(ks[1], (b, s, h, dk))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    ig = jax.random.normal(ks[3], (b, s, h))
    fg = jax.random.normal(ks[4], (b, s, h)) + 2.0

    out_c, _ = mlstm_chunked(q, k, v, ig, fg, chunk=32)

    state = (jnp.zeros((b, h, dv, dk)), jnp.zeros((b, h, dk)),
             jnp.full((b, h), -jnp.inf))
    outs = []
    for t in range(s):
        o, state = mlstm_step(state, q[:, t], k[:, t], v[:, t],
                              ig[:, t], fg[:, t])
        outs.append(o)
    out_r = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_r),
                               atol=2e-4, rtol=2e-3)
