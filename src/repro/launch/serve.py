"""Production serve driver: paged-KV continuous-batching engine.

  python -m repro.launch.serve --arch smollm-360m --requests 16 [--smoke]

Without ``--smoke`` the model has its published widths (the default
``smollm-360m`` fits one v5e); ``--smoke`` selects the reduced
same-family config.  The paged-attention kernel compiles on a TPU and
runs in interpret mode on any other backend.  At exit it prints, for
each span of the program, the count and the total, self and longest
seconds.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model
from repro.serving.engine import Engine, EngineConfig
from repro.util import trace


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="smollm-360m")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--page-tokens", type=int, default=16)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU tests)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke \
        else get_config(args.arch)
    if cfg.arch_kind not in ("dense", "vlm"):
        raise SystemExit(f"{args.arch}: paged engine serves the dense "
                         "family; use examples/ for SSM decode")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, EngineConfig(
        page_tokens=args.page_tokens,
        num_pages=max(1024, args.requests * 64)))
    print(f"[serve] {cfg.name} on {jax.default_backend()}, paged "
          f"attention {'interpreted' if eng.kernel_interpret else 'compiled'}")

    rng = np.random.RandomState(0)
    t0 = time.time()
    sids = [eng.add(rng.randint(0, cfg.vocab,
                                args.prompt_len).astype(np.int32),
                    max_new=args.max_new)
            for _ in range(args.requests)]
    steps = 0
    while any(not eng._requests[s].done for s in sids):
        eng.step()
        steps += 1
    dt = time.time() - t0
    tokens = sum(len(eng.result(s)) for s in sids)
    print(f"[serve] {args.requests} requests, {tokens} tokens, "
          f"{steps} steps, {dt:.2f}s → {tokens / dt:.1f} tok/s")
    print(f"[serve] page stats: {eng.cache.stats}")
    for name, row in trace.summary().items():
        print(f"[trace] {name}: {row}")


if __name__ == "__main__":
    main()
