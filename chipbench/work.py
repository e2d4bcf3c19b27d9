"""Work counts from shapes, kept with the benchmark.

Model FLOPs of a training step: 6 per matrix-multiply parameter per token
(forward 2, backward 4), plus attention's two products against the whole
sequence, 12 * layers * heads * head_dim * seq per token.  The embedding
lookup is a gather, not a product, so an untied input embedding does not
count; the output projection does.  Recomputation does not count.

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

from .reference import Dims


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
