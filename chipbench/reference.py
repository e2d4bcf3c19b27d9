"""Plain reference of a dense decoder-only transformer (the Llama and
Qwen2 families) and of AdamW, for the benchmark's correctness check.

Written from the published descriptions, in straightforward `jax.numpy`
at float32 with every matrix product at HIGHEST precision.  It imports
nothing from the program under test and takes nothing the program made:
its weights come from the seed by the weight recipe below, its rows from
the benchmark's own copy of the corpus.

* Block (HF `LlamaDecoderLayer` / `Qwen2DecoderLayer`): x += attn(rms(x));
  x += down(silu(gate(rms(x))) * up(rms(x))).  RMSNorm is
  x * rsqrt(mean(x^2) + eps) * w.  Rotary embedding on q and k with
  inv_freq = theta^(-2i/head_dim), halves rotated (`rotate_half`).  GQA:
  query head h reads kv head h // (heads / kv_heads).  Qwen2 adds biases
  to q, k and v.  Causal softmax attention, scaled by 1/sqrt(head_dim).
* Output: rms(x) @ W^T with W the unembedding, or the embedding where the
  two are tied; the loss is the mean token cross-entropy.
* Weight recipe (the benchmark's, from the seed): the leaves, named as
  below and taken in sorted name order, each get one key of
  `jax.random.split(key, n_leaves)`; embeddings are normal * 0.02,
  matrices normal / sqrt(fan_in), norm scales ones, biases zeros.  Layer
  leaves are stacked on a leading layer axis.
* AdamW (Loshchilov & Hutter) after clipping the global gradient norm;
  bias-corrected moments; decoupled weight decay on matrices only; linear
  warm-up from 0, then cosine decay to `min_lr_ratio` of the peak.

Memory: the gradient is accumulated one sequence at a time, each layer
is recomputed in the backward pass, and the AdamW moments wait on the
host between steps, so that the reference fits one chip.

`round_to` puts every matrix product's operands (and the embedded tokens)
through a lower precision on the forward pass: the benchmark's control,
the reference computed below the precision the configuration states.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    eps: float
    theta: float
    tied: bool
    qkv_bias: bool

    @staticmethod
    def from_hf(hf: dict) -> "Dims":
        """From a HF `config.json` of the Llama or Qwen2 families."""
        heads = hf["num_attention_heads"]
        return Dims(
            layers=hf["num_hidden_layers"], d=hf["hidden_size"],
            heads=heads, kv_heads=hf["num_key_value_heads"],
            head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
            d_ff=hf["intermediate_size"], vocab=hf["vocab_size"],
            eps=hf["rms_norm_eps"], theta=hf["rope_theta"],
            tied=hf["tie_word_embeddings"],
            qkv_bias=(hf["model_type"] == "qwen2"
                      or bool(hf.get("attention_bias", False))))


def leaf_specs(dm: Dims) -> Dict[str, tuple]:
    """name -> (shape, init), for every weight of the model."""
    L, d, hd = dm.layers, dm.d, dm.head_dim
    q, kv = dm.heads * hd, dm.kv_heads * hd
    s = {
        "embedding": ((dm.vocab, d), "normal"),
        "ln_f": ((d,), "ones"),
        "layers/ln": ((L, d), "ones"),
        "layers/ln2": ((L, d), "ones"),
        "layers/wq": ((L, d, q), "scaled"),
        "layers/wk": ((L, d, kv), "scaled"),
        "layers/wv": ((L, d, kv), "scaled"),
        "layers/wo": ((L, q, d), "scaled"),
        "layers/w_gate": ((L, d, dm.d_ff), "scaled"),
        "layers/w_up": ((L, d, dm.d_ff), "scaled"),
        "layers/w_down": ((L, dm.d_ff, d), "scaled"),
    }
    if dm.qkv_bias:
        s["layers/wq_b"] = ((L, q), "zeros")
        s["layers/wk_b"] = ((L, kv), "zeros")
        s["layers/wv_b"] = ((L, kv), "zeros")
    if not dm.tied:
        s["unembedding"] = ((dm.vocab, d), "normal")
    return dict(sorted(s.items()))


def init_params(dm: Dims, seed: int) -> Dict[str, jax.Array]:
    specs = leaf_specs(dm)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(specs))
        out = {}
        for (name, (shape, init)), k in zip(specs.items(), keys):
            if init == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
            elif init == "zeros":
                out[name] = jnp.zeros(shape, jnp.float32)
            elif init == "scaled":
                out[name] = (jax.random.normal(k, shape, jnp.float32)
                             * (1.0 / np.sqrt(shape[-2])))
            else:
                out[name] = jax.random.normal(k, shape, jnp.float32) * 0.02
        return out

    return make(jax.random.PRNGKey(seed))


def _rounder(dtype: Optional[str]):
    """Identity, or rounding through `dtype` on the forward pass with the
    gradient passed straight through."""
    if dtype is None:
        return lambda x: x

    @jax.custom_vjp
    def rnd(x):
        return x.astype(dtype).astype(jnp.float32)

    rnd.defvjp(lambda x: (rnd(x), None), lambda _, g: (g,))
    return rnd


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: [S, H, D]; HF rotate_half convention."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv            # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def row_loss(p, tokens, labels, dm: Dims, round_to: Optional[str] = None):
    """Mean cross-entropy of one sequence: tokens, labels [S]."""
    logits = row_logits(p, tokens, dm, round_to)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def row_logits(p, tokens, dm: Dims, round_to: Optional[str] = None):
    """Next-token logits [S, V] at every position of one sequence [S]."""
    rnd = _rounder(round_to)

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)

    S, H, KV, hd = tokens.shape[0], dm.heads, dm.kv_heads, dm.head_dim
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    x = rnd(jnp.take(p["embedding"], tokens, axis=0))

    def layer(x, lp):
        h = _rms(x, lp["ln"], dm.eps)
        q, k, v = mm(h, lp["wq"]), mm(h, lp["wk"]), mm(h, lp["wv"])
        if dm.qkv_bias:
            q, k, v = q + lp["wq_b"], k + lp["wk_b"], v + lp["wv_b"]
        q = _rope(q.reshape(S, H, hd), pos, dm.theta)
        k = _rope(k.reshape(S, KV, hd), pos, dm.theta)
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v.reshape(S, KV, hd), H // KV, axis=1)
        s = jnp.einsum("qhd,khd->hqk", rnd(q), rnd(k),
                       precision=HIGHEST) / np.sqrt(hd)
        pr = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", rnd(pr), rnd(v), precision=HIGHEST)
        x = x + mm(o.reshape(S, H * hd), lp["wo"])
        h = _rms(x, lp["ln2"], dm.eps)
        x = x + mm(jax.nn.silu(mm(h, lp["w_gate"])) * mm(h, lp["w_up"]),
                   lp["w_down"])
        return x, None

    lps = {k[len("layers/"):]: v for k, v in p.items()
           if k.startswith("layers/")}
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, lps)
    x = _rms(x, p["ln_f"], dm.eps)
    w = p["embedding"] if dm.tied else p["unembedding"]
    return jnp.einsum("sd,vd->sv", rnd(x), rnd(w), precision=HIGHEST)


@partial(jax.jit, static_argnames=("dm", "round_to"))
def token_gaps(p, tokens, dm: Dims, served, round_to: Optional[str] = None):
    """For a sequence [S] and the tokens [S] a server chose after each of
    its positions: how far below the reference's best logit each chosen
    token's logit lies.  With `round_to`, the chosen tokens are instead
    those the reference below its precision puts first."""
    ref = row_logits(p, tokens, dm)
    if round_to is not None:
        served = jnp.argmax(row_logits(p, tokens, dm, round_to), axis=-1)
    chosen = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - chosen


@partial(jax.jit, static_argnames=("dm", "round_to"))
def loss_and_grads(p, tokens, labels, dm: Dims,
                   round_to: Optional[str] = None):
    """Mean loss over the rows of tokens/labels [B, S] and its gradient,
    accumulated one row at a time."""
    vg = jax.value_and_grad(row_loss)

    def body(acc, row):
        l, g = vg(p, row[0], row[1], dm, round_to)
        return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

    zeros = jax.tree.map(jnp.zeros_like, p)
    (loss, grads), _ = jax.lax.scan(body, (jnp.float32(0), zeros),
                                    (tokens, labels))
    n = tokens.shape[0]
    return loss / n, jax.tree.map(lambda g: g / n, grads)


def _decays(name: str) -> bool:
    """Weight decay applies to matrices, not to norm scales or biases."""
    leaf = name.split("/")[-1]
    return not (leaf.startswith("ln") or leaf.endswith("_b"))


def learning_rate(hp: dict, count: int) -> float:
    if count < hp["warmup_steps"]:
        return hp["lr"] * count / max(1, hp["warmup_steps"])
    prog = min(1.0, max(0.0, (count - hp["warmup_steps"])
                        / max(1, hp["decay_steps"] - hp["warmup_steps"])))
    r = hp["min_lr_ratio"]
    return hp["lr"] * (r + (1 - r) * 0.5 * (1 + np.cos(np.pi * prog)))


@jax.jit
def _clip_scale(g, clip):
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    return jnp.minimum(1.0, clip / (gnorm + 1e-9))


@partial(jax.jit, donate_argnums=(0, 1), static_argnames=("decays",))
def _adamw_leaf(p, g, m, v, scale, lr, bc1, bc2, hp, decays: bool):
    g = g * scale
    m = hp["b1"] * m + (1 - hp["b1"]) * g
    v = hp["b2"] * v + (1 - hp["b2"]) * g * g
    upd = (m / bc1) / (jnp.sqrt(v / bc2) + hp["eps"])
    if decays:
        upd = upd + hp["weight_decay"] * p
    return p - lr * upd, g, m, v


def adamw_step(p, m, v, g, count: int, hp: dict):
    """One AdamW update (count = the step's 1-based number) of the device
    params `p` with the device gradient `g`; the moments `m`, `v` stay on
    the host between steps, so that the gradient's computation has the
    chip's memory to itself.  Returns the new params and moments and the
    clipped gradient the update used."""
    c = float(count)
    scale = (_clip_scale(g, hp["grad_clip"]) if hp["grad_clip"] is not None
             else jnp.float32(1))
    lr = jnp.float32(learning_rate(hp, count))
    bc1, bc2 = jnp.float32(1 - hp["b1"] ** c), jnp.float32(1 - hp["b2"] ** c)
    consts = {k: hp[k] for k in ("b1", "b2", "eps", "weight_decay")}
    p, g = dict(p), dict(g)
    for k in sorted(p):
        p[k], g[k], mk, vk = _adamw_leaf(p[k], g[k], m[k], v[k], scale, lr,
                                         bc1, bc2, consts,
                                         decays=_decays(k))
        m[k], v[k] = jax.device_get((mk, vk))
    return p, m, v, g


@jax.jit
def leaf_norms(tree):
    """L2 norm of each leaf; stacked layer leaves get one norm a layer."""
    def norm(name, x):
        x = x.astype(jnp.float32)
        if name.startswith("layers/"):
            return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        return jnp.sqrt(jnp.sum(x * x))
    return {k: norm(k, x) for k, x in tree.items()}


@jax.jit
def diff_norms(a, b):
    return leaf_norms({k: a[k] - b[k] for k in a})


def run_steps(dm: Dims, seed: int, rows, hp: dict, steps: int,
              round_to: Optional[str] = None, p3_of=None) -> dict:
    """Train the reference from the seed's weights on `rows` ([steps] of
    (tokens, labels) host arrays [B, S]).  Returns each step's loss, the
    leaf norms of the first clipped gradient, and the leaf norms of the
    parameters' change after `steps` steps, and of `p3_of` (host leaves,
    the same names) minus the initial weights where given."""
    p = init_params(dm, seed)
    prog_change = None
    if p3_of is not None:
        prog_change = jax.device_get(diff_norms(
            {k: jnp.asarray(p3_of[k]) for k in p}, p))
    m = {k: np.zeros(x.shape, np.float32) for k, x in p.items()}
    v = {k: np.zeros(x.shape, np.float32) for k, x in p.items()}
    losses, g1 = [], None
    for i in range(steps):
        tokens, labels = rows[i]
        loss, g = loss_and_grads(p, jnp.asarray(tokens), jnp.asarray(labels),
                                 dm, round_to)
        losses.append(float(loss))
        p, m, v, g = adamw_step(p, m, v, g, i + 1, hp)
        if i == 0:
            g1 = jax.device_get(leaf_norms(g))
        del g
    del m, v
    # the initial weights again, from the seed, rather than a second copy
    # held through the steps
    change = jax.device_get(diff_norms(p, init_params(dm, seed)))
    return {"losses": losses, "grad_norms": g1, "change_norms": change,
            "prog_change_norms": prog_change}
