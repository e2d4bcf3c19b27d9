"""The dense decoder-only transformer (the Llama and Qwen2 families): its
sizes from a HF `config.json`, the program's configuration, its weights,
its plain forward pass and its work counts.

* Block (HF `LlamaDecoderLayer` / `Qwen2DecoderLayer`): x += attn(rms(x));
  x += down(silu(gate(rms(x))) * up(rms(x))).  RMSNorm is
  x * rsqrt(mean(x^2) + eps) * w.  Rotary embedding on q and k with
  inv_freq = theta^(-2i/head_dim), halves rotated (`rotate_half`).  GQA:
  query head h reads kv head h // (heads / kv_heads).  Qwen2 adds biases
  to q, k and v.  Causal softmax attention, scaled by 1/sqrt(head_dim).
* Output: rms(x) @ W^T with W the unembedding, or the embedding where the
  two are tied; the loss is the mean token cross-entropy, with no
  auxiliary term and no statistics.
* Weights, by the benchmark's recipe (`reference.init_params`) over
  `leaf_specs`: embeddings "normal", matrices "scaled", norm scales
  "ones", biases "zeros"; every leaf moves by its gradient.
* Departures from the HF code: none in the arithmetic.  Each layer is
  recomputed in the backward pass (`jax.checkpoint` in the scan), which
  saves memory and changes no value.

Work counts, from shapes.  Model FLOPs of a training step: 6 per
matrix-multiply parameter per token (forward 2, backward 4), plus
attention's two products against the whole sequence, 12 * layers * heads
* head_dim * seq per token.  The embedding lookup is a gather, not a
product, so an untied input embedding does not count; the output
projection does.  Recomputation does not count.

Serving counts the forward alone: 2 per matrix-multiply parameter per
token, plus 4 * layers * heads * head_dim per token of context the token
attends to.

Paged decode attention reads, for each sequence, the K and V of every
token of its context once, in every layer, and its query and output once:
it is bound by memory (2 FLOPs a context element for each of QK and PV,
about 2 * group / bytes-per-element FLOP a byte, far under the v5e's
ridge of 240), so its roofline is its bytes over the HBM bandwidth.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import HIGHEST, _rms, _rope, _rounder, cross_entropy

# the smoke cell's model: every width cut, the shape of the block kept
TINY = {"num_hidden_layers": 2, "hidden_size": 64,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 128, "vocab_size": 256}


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


def dims(hf: dict) -> Dims:
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


def program_config(conf: dict):
    """The program's ModelConfig for a configuration file: the registered
    architecture with the file's published sizes and pinned options."""
    from repro.configs import get_config
    dm = dims(conf)
    return get_config(conf["program"]["arch"]).replace(
        n_layers=dm.layers, d_model=dm.d, n_heads=dm.heads,
        n_kv_heads=dm.kv_heads, head_dim=dm.head_dim, d_ff=dm.d_ff,
        vocab=dm.vocab, qkv_bias=dm.qkv_bias, tie_embeddings=dm.tied,
        rope_theta=dm.theta, norm_eps=dm.eps,
        **conf["program"].get("options", {}))


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


def row_loss(p, tokens, labels, dm: Dims, round_to: Optional[str] = None):
    """Mean cross-entropy of one sequence: tokens, labels [S]; no
    statistics."""
    return cross_entropy(row_logits(p, tokens, dm, round_to), labels), {}


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


def after_update(p, stats, dm: Dims):
    """Every leaf moves by its gradient: nothing to update."""
    return p


def non_gradient_leaves(dm: Dims) -> frozenset:
    return frozenset()


def train_flops_per_step(dm: Dims, batch: int, seq: int,
                         step_metrics) -> float:
    """The same in every step (`train_flops_per_token`)."""
    return train_flops_per_token(dm, seq) * batch * seq


def matmul_params(dm: Dims) -> int:
    q, kv = dm.heads * dm.head_dim, dm.kv_heads * dm.head_dim
    per_layer = dm.d * q + 2 * dm.d * kv + q * dm.d + 3 * dm.d * dm.d_ff
    return dm.layers * per_layer + dm.vocab * dm.d


def train_flops_per_token(dm: Dims, seq: int) -> float:
    attn = 12 * dm.layers * dm.heads * dm.head_dim * seq
    return 6.0 * matmul_params(dm) + attn


def forward_flops(dm: Dims, tokens: int, context_tokens: int) -> float:
    """Forward FLOPs of `tokens` new tokens that attend, together, to
    `context_tokens` positions (the sum over the new tokens of each one's
    context length, itself included)."""
    return (2.0 * matmul_params(dm) * tokens
            + 4.0 * dm.layers * dm.heads * dm.head_dim * context_tokens)


def paged_attention_bytes(dm: Dims, batch: int, context_tokens: int,
                          kv_bytes: int, act_bytes: int) -> float:
    """Bytes one decode step's paged attention must move in all layers:
    K and V of every context token (`context_tokens` summed over the
    batch) for each KV head, and each sequence's query and output."""
    kv = 2 * context_tokens * dm.kv_heads * dm.head_dim * kv_bytes
    q_out = 2 * batch * dm.heads * dm.head_dim * act_bytes
    return dm.layers * (kv + q_out)
