"""Batched serving with the paged KV engine: continuous batching, prefix
sharing (WTF `copy` on KV pages), and the Pallas paged-attention kernel.

  PYTHONPATH=src python examples/serve_lm.py
"""
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0] + "/src")

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.models import get_model
from repro.serving.engine import Engine, EngineConfig


def main():
    cfg = get_smoke_config("qwen2-7b")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params,
                 EngineConfig(page_tokens=8, num_pages=512))

    rng = np.random.RandomState(0)
    system_prompt = rng.randint(0, cfg.vocab, 24).astype(np.int32)

    # eight requests sharing the same 24-token system prompt: the shared
    # pages are forked (refcounted), not copied
    base = eng.add(system_prompt, max_new=8)
    t0 = time.time()
    sids = [base]
    for i in range(7):
        user = rng.randint(0, cfg.vocab, 8).astype(np.int32)
        sids.append(eng.add(np.concatenate([system_prompt, user]),
                            max_new=8, fork_from=base))
    steps = 0
    while any(not eng._requests[s].done for s in sids):
        eng.step()
        steps += 1
    dt = time.time() - t0
    stats = eng.cache.stats
    print(f"[serve] 8 requests × 8 tokens in {steps} batched steps, "
          f"{dt:.2f}s")
    print(f"[serve] pages: allocated={stats['pages_allocated']} "
          f"shared={stats['pages_shared']} cow={stats['pages_copied']}")
    for s in sids[:3]:
        print(f"[serve] seq {s}: {eng.result(s)}")
    total_tokens = sum(len(eng.result(s)) for s in sids)
    kernel = "interpret-mode" if eng.kernel_interpret else "compiled"
    print(f"[serve] throughput: {total_tokens / dt:.1f} tok/s "
          f"({jax.default_backend()}, {kernel} kernel)")


if __name__ == "__main__":
    main()
