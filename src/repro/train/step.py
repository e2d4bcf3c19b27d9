"""train_step / serve_step builders.

`make_train_step` returns a pure function (state, batch) -> (state, metrics)
with optional gradient-accumulation microbatching (a `lax.scan` over
microbatches — activation memory scales with batch/accum_steps while the
gradient buffer stays whole, which is what makes the biggest train cells
fit HBM).  `make_serve_step` returns (params, cache, batch) ->
(next_tokens, cache) — one decoded token against the KV/state cache.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import Model, get_model
from repro.models.layers import cross_entropy
from . import optimizer as opt


@dataclass(frozen=True)
class TrainHyper:
    adamw: opt.AdamWConfig = opt.AdamWConfig()
    accum_steps: int = 1            # gradient-accumulation microbatches
    z_loss: float = 0.0             # logit-norm regularizer (0 = off)


def make_loss_fn(model: Model, rules=None) -> Callable:
    cfg = model.cfg

    def loss_fn(params, batch):
        if cfg.moe is not None:
            logits, aux = model.module.forward(params, batch, cfg,
                                               rules=rules, return_aux=True)
        else:
            logits = model.module.forward(params, batch, cfg, rules=rules)
            aux = 0.0
        loss = cross_entropy(logits, batch["labels"])
        return loss + aux, {"ce": loss, "aux": jnp.asarray(aux)}

    return loss_fn


def init_state(model: Model, rng: jax.Array) -> Dict[str, Any]:
    params = model.init(rng)
    return {"params": params, "opt": opt.init(params),
            "step": jnp.zeros((), jnp.int32)}


def abstract_state(model: Model) -> Dict[str, Any]:
    """ShapeDtypeStruct state for the AOT dry-run (no allocation)."""
    params = model.abstract_params()
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)  # noqa: E731
    return {
        "params": params,
        "opt": opt.OptState(m=jax.tree.map(f32, params),
                            v=jax.tree.map(f32, params),
                            count=jax.ShapeDtypeStruct((), jnp.int32)),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }


def make_train_step(model: Model, hyper: TrainHyper = TrainHyper(),
                    rules=None) -> Callable:
    loss_fn = make_loss_fn(model, rules)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    k = hyper.accum_steps

    def single(params, batch):
        (loss, parts), grads = grad_fn(params, batch)
        return loss, parts, grads

    def accumulated(params, batch):
        def micro(carry, mb):
            acc, loss_acc = carry
            (loss, _), grads = grad_fn(params, mb)
            acc = jax.tree.map(jnp.add, acc, grads)
            return (acc, loss_acc + loss), None

        mbs = jax.tree.map(
            lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]), batch)
        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss), _ = jax.lax.scan(micro, (zeros, 0.0), mbs)
        grads = jax.tree.map(lambda g: g / k, grads)
        return loss / k, {}, grads

    def train_step(state, batch):
        if k > 1:
            loss, parts, grads = accumulated(state["params"], batch)
        else:
            loss, parts, grads = single(state["params"], batch)
        params, opt_state, om = opt.update(hyper.adamw, grads,
                                           state["opt"], state["params"])
        metrics = {"loss": loss, **parts, **om}
        return ({"params": params, "opt": opt_state,
                 "step": state["step"] + 1}, metrics)

    return train_step


REMAT_POLICIES = ("none", "dots", "full")       # fastest first


def fit_remat(model: Model, hyper: TrainHyper, batch: int,
              seq: int) -> Model:
    """The model under the fastest remat policy whose train step, at
    ``batch`` x ``seq`` tokens, the compiler fits into the default
    device's memory; prints each attempt with its compile time.  A
    backend that reports no memory limit (the CPU) gets the model back
    unchanged."""
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    if limit is None:
        return model
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    for remat in REMAT_POLICIES:
        m = get_model(model.cfg.replace(remat=remat))
        step = jax.jit(make_train_step(m, hyper), donate_argnums=(0,))
        t0 = time.perf_counter()
        try:
            mem = step.lower(abstract_state(m), {
                "tokens": tokens, "labels": tokens}).compile() \
                .memory_analysis()
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            print(f"[fit_remat] remat={remat}: refused in "
                  f"{time.perf_counter() - t0:.1f}s: "
                  f"{str(e).splitlines()[0][:160]}", flush=True)
            continue
        need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        print(f"[fit_remat] remat={remat}: needs {need / 1e9:.2f} GB of "
              f"{limit / 1e9:.2f} GB, compiled in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
        if need <= limit:
            return m
    raise ValueError(f"{model.cfg.name}: no remat policy fits a "
                     f"{batch}x{seq} train step in {limit} bytes")


def make_serve_step(model: Model, rules=None) -> Callable:
    def serve_step(params, cache, batch):
        logits, cache = model.module.decode_step(params, cache, batch,
                                                 model.cfg, rules=rules)
        next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return next_tok, cache

    return serve_step


def make_prefill_step(model: Model, rules=None) -> Callable:
    """Full-sequence forward that returns last-position logits (serving
    prefill; decode then continues against the cache built by the engine)."""
    def prefill(params, batch):
        logits = model.module.forward(params, batch, model.cfg,
                                      rules=rules)
        return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)

    return prefill
