"""Plain reference for the benchmark's correctness check: the weight
recipe, a model's loss and gradient one sequence at a time, and AdamW.
The model itself, its sizes, weights and forward pass, is its family's
(`families/<family>.py`); this module knows no model's shape.

Written from the published descriptions, in straightforward `jax.numpy`
at float32 with every matrix product at HIGHEST precision.  It imports
nothing from the program under test and takes nothing the program made:
its weights come from the seed by the weight recipe below, its rows from
the benchmark's own copy of the corpus.  A family's forward pass is
written the same way, with the helpers here (`_rounder`, `_rms`,
`_rope`, `cross_entropy`).

* Weight recipe (the benchmark's, from the seed): the leaves of the
  family's `leaf_specs`, taken in sorted name order, each get one key of
  `jax.random.split(key, n_leaves)`; "normal" leaves (embeddings) are
  normal * 0.02, "scaled" ones (matrices) normal / sqrt(fan_in), "ones"
  and "zeros" (norm scales, biases) constant.  Layer leaves are stacked
  on a leading layer axis.
* Loss: the mean over the rows of the family's `row_loss`.  The per-row
  statistics it returns are summed over the rows and handed, after each
  step's update, to the family's `after_update`, which moves the leaves
  no gradient moves (`non_gradient_leaves`); AdamW leaves those alone.
* AdamW (Loshchilov & Hutter) after clipping the global gradient norm;
  bias-corrected moments; decoupled weight decay on matrices only; linear
  warm-up from 0, then cosine decay to `min_lr_ratio` of the peak.

Memory: the gradient is accumulated one sequence at a time, and the
AdamW moments wait on the host between steps, so that the reference fits
one chip.

`round_to` puts every matrix product's operands (and the embedded tokens)
through a lower precision on the forward pass: the benchmark's control,
the reference computed below the precision the configuration states.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def init_params(fam, dm, seed: int) -> Dict[str, jax.Array]:
    """The weights of the family `fam` at sizes `dm`, from the seed."""
    specs = fam.leaf_specs(dm)

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


def cross_entropy(logits, labels):
    """Mean token cross-entropy of logits [S, V] against labels [S]."""
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


@partial(jax.jit, static_argnames=("fam", "dm", "round_to"))
def token_gaps(fam, p, tokens, dm, served, round_to: Optional[str] = None):
    """For a sequence [S] and the tokens [S] a server chose after each of
    its positions: how far below the reference's best logit each chosen
    token's logit lies.  With `round_to`, the chosen tokens are instead
    those the reference below its precision puts first."""
    ref = fam.row_logits(p, tokens, dm)
    if round_to is not None:
        served = jnp.argmax(fam.row_logits(p, tokens, dm, round_to), axis=-1)
    chosen = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - chosen


@partial(jax.jit, static_argnames=("fam", "dm", "round_to"))
def loss_and_grads(fam, p, tokens, labels, dm,
                   round_to: Optional[str] = None):
    """Mean loss over the rows of tokens/labels [B, S] and its gradient,
    accumulated one row at a time, and the rows' statistics summed."""
    def row(p, tokens, labels):
        return fam.row_loss(p, tokens, labels, dm, round_to)

    vg = jax.value_and_grad(row, has_aux=True)

    def body(acc, r):
        (l, st), g = vg(p, r[0], r[1])
        return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g),
                jax.tree.map(jnp.add, acc[2], st)), None

    zeros = jax.tree.map(jnp.zeros_like, p)
    no_stats = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            jax.eval_shape(row, p, tokens[0], labels[0])[1])
    (loss, grads, stats), _ = jax.lax.scan(
        body, (jnp.float32(0), zeros, no_stats), (tokens, labels))
    n = tokens.shape[0]
    return loss / n, jax.tree.map(lambda g: g / n, grads), stats


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


def adamw_step(p, m, v, g, count: int, hp: dict, frozen: frozenset):
    """One AdamW update (count = the step's 1-based number) of the device
    params `p` with the device gradient `g`; the moments `m`, `v` stay on
    the host between steps, so that the gradient's computation has the
    chip's memory to itself.  The leaves in `frozen` are neither updated
    nor counted in the clipped norm.  Returns the new params and moments
    and the clipped gradient the update used."""
    c = float(count)
    scale = (_clip_scale({k: x for k, x in g.items() if k not in frozen},
                         hp["grad_clip"]) if hp["grad_clip"] is not None
             else jnp.float32(1))
    lr = jnp.float32(learning_rate(hp, count))
    bc1, bc2 = jnp.float32(1 - hp["b1"] ** c), jnp.float32(1 - hp["b2"] ** c)
    consts = {k: hp[k] for k in ("b1", "b2", "eps", "weight_decay")}
    p, g = dict(p), dict(g)
    for k in sorted(set(p) - frozen):
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


def run_steps(fam, dm, seed: int, rows, hp: dict, steps: int,
              round_to: Optional[str] = None, p3_of=None) -> dict:
    """Train the reference of the family `fam` at sizes `dm` from the
    seed's weights on `rows` ([steps] of (tokens, labels) host arrays
    [B, S]); after each step's AdamW update, the family's `after_update`
    gets the step's summed row statistics.  Returns each step's loss, the
    leaf norms of the first clipped gradient, and the leaf norms of the
    parameters' change after `steps` steps, and of `p3_of` (host leaves,
    the same names) minus the initial weights where given."""
    frozen = fam.non_gradient_leaves(dm)
    p = init_params(fam, dm, seed)
    prog_change = None
    if p3_of is not None:
        prog_change = jax.device_get(diff_norms(
            {k: jnp.asarray(p3_of[k]) for k in p}, p))
    m = {k: np.zeros(x.shape, np.float32) for k, x in p.items()}
    v = {k: np.zeros(x.shape, np.float32) for k, x in p.items()}
    losses, g1 = [], None
    for i in range(steps):
        tokens, labels = rows[i]
        loss, g, stats = loss_and_grads(fam, p, jnp.asarray(tokens),
                                        jnp.asarray(labels), dm, round_to)
        losses.append(float(loss))
        p, m, v, g = adamw_step(p, m, v, g, i + 1, hp, frozen)
        p = fam.after_update(p, stats, dm)
        if i == 0:
            g1 = jax.device_get(leaf_norms(g))
        del g
    del m, v
    # the initial weights again, from the seed, rather than a second copy
    # held through the steps
    change = jax.device_get(diff_norms(p, init_params(fam, dm, seed)))
    return {"losses": losses, "grad_norms": g1, "change_norms": change,
            "prog_change_norms": prog_change}
