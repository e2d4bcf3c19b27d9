"""Helpers every driver shares: the log, seeds, leaves by path and their
checksum, host spans, and the record a per-layer metric reads."""
from __future__ import annotations

import collections
import contextlib
import sys
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.device_trace import span


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def sub_seeds(seed: int) -> Dict[str, int]:
    """Independent 31-bit seeds for the corpus, the weights and the
    shuffle, from any whole number."""
    s = np.random.SeedSequence(seed).generate_state(3)
    return {"data": int(s[0]), "init": int(s[1] >> 1),
            "shuffle": int(s[2] >> 1)}


def flat(tree) -> Dict[str, jax.Array]:
    """Leaves by path, `a/b/c`."""
    def key(k):
        for attr in ("key", "name", "idx"):
            if hasattr(k, attr):
                return str(getattr(k, attr))
        return str(k)
    return {"/".join(key(k) for k in path): x
            for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


@jax.jit
def fingerprint(tree):
    """Two wrapping 32-bit sums of each leaf's words, the second weighted
    by position: a changed, lost or moved word changes them."""
    def fp(x):
        w = jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
        i = jnp.arange(1, w.shape[0] + 1, dtype=jnp.uint32)
        return jnp.stack([jnp.sum(w, dtype=jnp.uint32),
                          jnp.sum(w * i, dtype=jnp.uint32)])
    return {k: fp(x) for k, x in tree.items()}


class Spans:
    """Host-clock spans, also written into the trace."""

    def __init__(self):
        self.times: Dict[str, List[tuple]] = collections.defaultdict(list)

    @contextlib.contextmanager
    def __call__(self, name: str):
        with span(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.times[name].append((t0, time.perf_counter()))

    def durations(self, name: str, since: float) -> List[float]:
        return [b - a for a, b in self.times[name] if a >= since]


class RunData:
    """What a per-layer metric's reader may read from a run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
