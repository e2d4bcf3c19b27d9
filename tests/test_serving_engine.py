"""Paged serving engine: outputs must match the dense ring-cache decode
path exactly; prefix forking must share pages (zero-copy) and still
produce independent continuations.  Each runs at pages of 4 tokens (one
folded row a page, for the smoke's head dim of 32) and of 16 (four rows
a page), and the decode step consumes the pools it is given."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import get_model
from repro.serving.engine import Engine, EngineConfig
from repro.train import make_serve_step
from repro.util import trace

PAGE_TOKENS = pytest.mark.parametrize("page_tokens", [4, 16])


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("smollm-360m").replace(compute_dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _reference_generate(model, params, prompt, max_new):
    """Greedy decode via the model's ring-buffer cache path."""
    cfg = model.cfg
    cache = model.init_cache(1, max_len=len(prompt) + max_new)
    serve = make_serve_step(model)
    out = []
    tok = None
    for t in range(len(prompt) + max_new - 1):
        cur = prompt[t] if t < len(prompt) else out[-1]
        tok, cache = serve(params, cache,
                           {"tokens": jnp.asarray([[cur]], jnp.int32),
                            "pos": jnp.asarray([t], jnp.int32)})
        if t >= len(prompt) - 1:
            out.append(int(tok[0]))
    return out[:max_new]


@PAGE_TOKENS
def test_engine_matches_reference(setup, page_tokens):
    cfg, model, params = setup
    eng = Engine(model, params, EngineConfig(page_tokens=page_tokens,
                                             num_pages=128))
    prompt = np.array([5, 9, 2, 7, 11, 3], np.int32)
    sid = eng.add(prompt, max_new=6)
    while not eng._requests[sid].done:
        eng.step()
    ref = _reference_generate(model, params, list(prompt), 6)
    assert eng.result(sid) == ref


@PAGE_TOKENS
def test_engine_batched_requests(setup, page_tokens):
    cfg, model, params = setup
    eng = Engine(model, params, EngineConfig(page_tokens=page_tokens,
                                             num_pages=256))
    prompts = [np.array(p, np.int32) for p in
               ([1, 2, 3], [10, 20, 30, 40, 50], [7, 7, 7, 7])]
    sids = [eng.add(p, max_new=4) for p in prompts]
    for _ in range(8):
        eng.step()
    for sid, p in zip(sids, prompts):
        ref = _reference_generate(model, params, list(p), 4)
        assert eng.result(sid) == ref, sid


@PAGE_TOKENS
def test_prefix_fork_shares_pages_and_diverges(setup, page_tokens):
    cfg, model, params = setup
    eng = Engine(model, params, EngineConfig(page_tokens=page_tokens,
                                             num_pages=256))
    base = np.resize(np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int32),
                     2 * page_tokens)                     # 2 full pages
    a = eng.add(base, max_new=4)
    # fork with the same page-aligned prefix + 2 extra tokens
    b = eng.add(np.concatenate([base, [8, 8]]).astype(np.int32),
                max_new=4, fork_from=a)
    assert eng.cache.stats["pages_shared"] >= 2        # prefix pages shared
    while not (eng._requests[a].done and eng._requests[b].done):
        eng.step()
    ref_a = _reference_generate(model, params, list(base), 4)
    ref_b = _reference_generate(model, params, list(base) + [8, 8], 4)
    assert eng.result(a) == ref_a
    assert eng.result(b) == ref_b


@PAGE_TOKENS
def test_fork_mid_page_cow(setup, page_tokens):
    cfg, model, params = setup
    eng = Engine(model, params, EngineConfig(page_tokens=page_tokens,
                                             num_pages=256))
    base = np.resize(np.array([3, 1, 4, 1, 5, 9], np.int32),
                     page_tokens + page_tokens // 2)      # 1.5 pages
    a = eng.add(base, max_new=3)
    b = eng.add(np.concatenate([base, [2, 2]]).astype(np.int32),
                max_new=3, fork_from=a)
    assert eng.cache.stats["pages_copied"] >= 1        # open page COW'd
    while not (eng._requests[a].done and eng._requests[b].done):
        eng.step()
    assert eng.result(a) == _reference_generate(model, params,
                                                list(base), 3)
    assert eng.result(b) == _reference_generate(
        model, params, list(base) + [2, 2], 3)


def test_step_donates_pools(setup):
    cfg, model, params = setup
    eng = Engine(model, params, EngineConfig(page_tokens=16, num_pages=64))
    eng.add(np.array([5, 9, 2, 7, 11], np.int32), max_new=3)
    pools_in = (eng.k_pool, eng.v_pool)
    eng.step()
    assert all(p.is_deleted() for p in pools_in)
    assert trace.records("engine.step")[-1].attrs["pools_donated"] == 1
    assert not eng.k_pool.is_deleted() and not eng.v_pool.is_deleted()
