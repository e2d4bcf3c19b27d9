"""The main path's programs compile for a TPU v5e that is described, not
attached: the Pallas kernels at the widths `chip_smoke.py` serves and
trains at, the full-width decode step, which must update its donated
pools in place, and the full-width train step, which must fit one chip's
HBM.  Nothing here runs; a compile that the chip's compiler refuses
(tiling, VMEM, HBM) fails the test.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every pytest worker imports this file."""
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke as smoke
from repro.configs import get_config
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.paged_attention.kernel import (paged_attention_kernel,
                                                  pool_shape)
from repro.models import get_model
from repro.serving.engine import _decode_step
from repro.train import abstract_state, make_train_step

HBM_BYTES = 16_909_336_064  # 15.75 GiB: the bytes_limit of one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _on(sharding, tree):
    return jax.tree.map(lambda s: _spec(sharding, s.shape, s.dtype), tree)


def _hbm_need(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


@pytest.mark.parametrize("dtype,batch,pages,pool_pages", [
    pytest.param("bfloat16", smoke.SERVE_BATCH, smoke.SERVE_TABLE_PAGES,
                 smoke.NUM_PAGES,
                 id=f"bfloat16-{smoke.SERVE_BATCH}-{smoke.SERVE_TABLE_PAGES}"),
    pytest.param("float32", 1, -(-(smoke.PROMPT_LEN + smoke.MAX_NEW - 1)
                                 // smoke.PAGE_TOKENS), smoke.NUM_PAGES,
                 id="float32-1-4"),
    # the serving benchmark's batch, pool and widest table: six blocks of
    # 16 pages for 48 sequences
    pytest.param("bfloat16", 48, 96, 5120, id="bfloat16-48-96-serve"),
])
def test_paged_attention_kernel_compiles(one_chip, dtype, batch, pages,
                                         pool_pages):
    cfg = get_config(smoke.ARCH)
    d, hkv = cfg.head_dim_, cfg.n_kv_heads
    pool = pool_shape(cfg.n_layers, hkv, pool_pages, smoke.PAGE_TOKENS, d)
    compiled = jax.jit(paged_attention_kernel).lower(
        _spec(one_chip, (batch, cfg.n_heads, d), dtype),
        _spec(one_chip, pool, dtype), _spec(one_chip, pool, dtype),
        _spec(one_chip, (batch, pages), "int32"),
        _spec(one_chip, (batch,), "int32"),
        _spec(one_chip, (), "int32")).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_flash_attention_compiles(one_chip, direction):
    cfg = get_config(smoke.ARCH)
    q = _spec(one_chip, (1, 2048, cfg.n_heads, cfg.head_dim_), "bfloat16")
    kv = _spec(one_chip, (1, 2048, cfg.n_kv_heads, cfg.head_dim_),
               "bfloat16")
    fn = flash_attention
    if direction == "backward":
        # the backward recomputes with the XLA reference; the value keeps
        # the forward kernel in the program, as in a train step
        fn = jax.value_and_grad(lambda q, k, v: flash_attention(
            q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    compiled = jax.jit(fn).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _pool_copies(hlo: str, pool_pages: int, layer_elems: int) -> list:
    """The shapes of the arrays that copy instructions write which have
    a dimension of the pools' `pool_pages` pages and hold one layer's
    pool or more.  (A copy of a parameter, such as the embedding's into
    another memory space, has no such dimension.)"""
    shapes = [[int(n) for n in dims.split(",") if n]
              for line in hlo.splitlines()
              if re.search(r" copy(-start)?\(", line)
              for dims in re.findall(r"\w+\[([\d,]*)\]",
                                     line.split(" copy")[0])]
    return [s for s in shapes
            if pool_pages in s and math.prod(s) >= layer_elems]


@pytest.mark.parametrize("batch,pages,pool_pages", [
    pytest.param(smoke.SERVE_BATCH, smoke.SERVE_TABLE_PAGES,
                 smoke.NUM_PAGES, id="smoke"),
    # the serving benchmark's batch, widest table and pool
    pytest.param(48, 96, 5120, id="serve"),
])
def test_full_width_decode_step_compiles(one_chip, batch, pages,
                                         pool_pages):
    cfg = get_config(smoke.ARCH)
    model = get_model(cfg)
    t, hkv, d = smoke.PAGE_TOKENS, cfg.n_kv_heads, cfg.head_dim_
    pool = pool_shape(cfg.n_layers, hkv, pool_pages, t, d)
    step = jax.jit(functools.partial(
        _decode_step, cfg=cfg, page_tokens=t, interpret=False),
        donate_argnums=(1, 2))
    compiled = step.lower(
        _on(one_chip, model.abstract_params()),
        _spec(one_chip, pool, cfg.compute_dtype),
        _spec(one_chip, pool, cfg.compute_dtype),
        _spec(one_chip, (batch, 1), "int32"),
        _spec(one_chip, (batch,), "int32"),
        _spec(one_chip, (batch, pages), "int32")).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert _hbm_need(compiled) < HBM_BYTES
    # the step updates both pools in place: they are its outputs' buffers,
    # and no copy writes a pool or a layer of one
    pool_bytes = math.prod(pool) * jnp.dtype(cfg.compute_dtype).itemsize
    assert compiled.memory_analysis().alias_size_in_bytes >= 2 * pool_bytes
    assert _pool_copies(hlo, pool_pages, hkv * pool_pages * t * d) == []


def test_full_width_train_step_fits_one_chip(one_chip):
    """At the smoke's batch x seq the step fits one chip only with full
    remat: without it the compiler asks for ~40 GB."""
    cfg = get_config(smoke.ARCH).replace(remat="full")
    model = get_model(cfg)
    tokens = _spec(one_chip, (smoke.TRAIN_BATCH, smoke.TRAIN_SEQ), "int32")
    compiled = jax.jit(make_train_step(model, smoke.HYPER),
                       donate_argnums=(0,)).lower(
        _on(one_chip, abstract_state(model)),
        {"tokens": tokens, "labels": tokens}).compile()
    assert _hbm_need(compiled) < HBM_BYTES
