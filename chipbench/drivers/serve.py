"""Serving traffic: chat sessions that share one system prompt, as a closed
loop of callers, each waiting for its reply before it sends its next turn.

A session's turn is `user_tokens` new user tokens and `new_tokens`
generated ones.  Its turn k (of `turns`) forks its turn k - 1
(`fork_from`): the prompt is that turn's prompt, its answer and the new
user tokens, and the forked turn is then released.  Turn 0 forks the
system prompt; after turn `turns - 1` the session starts again.  Sessions
are staggered: in wave w session i is at turn (i + w) % turns, so every
wave holds every prompt length.

Set-up: the weights from the seed (one jitted call, float32 as the engine
serves them), an `Engine` with the mix's page size and pool, the system
prompt prefilled, and wave 0, served like the window's: the sessions'
previous turns, whose history before them is drawn from the seed (each
forks the system prompt).  It runs every prefill and decode shape the
window runs; one more turn then forks a previous turn mid-page, as the
window's turns do (the engine copies that page), and is released.  All of
it goes through `Engine.add`, `Engine.step` and `Engine.release`.

Window: waves 1 to `waves` (the cell's nominal wave time into
`--seconds`, at least one).  Every caller sends its turn as soon as its
reply has come, so a wave is submitted when the one before it has been
served, the first at the window's opening.  The engine admits a wave's
turns one by one (`Engine.add` prefills a turn and returns its first
token), in an order that interleaves the turn indices (which session of
an index comes first is drawn from the seed), and then decodes them
together (`Engine.step`) until each has `new_tokens` tokens.  A turn's
TTFT runs from its wave's submission to its first token on the host;
`decode_tokens_per_s` is every token generated in the window over the
window.

Checked once the window has closed and the engine is freed: every turn
finished with `new_tokens` tokens of the vocabulary; and a sample of the
window's turns drawn from the seed, `checked_per_turn` of each turn index
(so the longest prompts are in it), against the reference: `served_gap`
is the widest gap by which a served token's logit lies below the
reference's best.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import math
import tempfile
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import get_model
from repro.serving.engine import Engine, EngineConfig

from chipbench import device_trace, reference, spec
from chipbench.common import RunData, Spans, log, sub_seeds
from chipbench.device_trace import span

CONTROL_DTYPE = "float8_e4m3fn"   # below the engine's bfloat16 compute


def nest(flat: Dict[str, jax.Array]) -> dict:
    """`layers/wq` -> {"layers": {"wq": ...}}: the program's tree."""
    out: dict = {}
    for name, x in flat.items():
        node = out
        *path, leaf = name.split("/")
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = x
    return out


def prompt_lengths(tr: dict) -> List[int]:
    """Prompt length of each turn index."""
    return [tr["system_tokens"] + (k + 1) * tr["user_tokens"]
            + k * tr["new_tokens"] for k in range(tr["turns"])]


def admission_order(n_sess: int, turns: int, wave: int,
                    rng: np.random.Generator) -> List[int]:
    """The sessions of a wave in the order the engine admits them: turn
    indices interleaved (0, 1, ..., turns - 1, 0, 1, ...), so that every
    seed admits the same lengths in the same order; within an index, an
    order drawn from the seed."""
    groups = [rng.permutation([i for i in range(n_sess)
                               if (i + wave) % turns == k]).tolist()
              for k in range(turns)]
    order = []
    for j in range(max(len(g) for g in groups)):
        order += [g[j] for g in groups if j < len(g)]
    return order


class Session:
    __slots__ = ("prompt", "answer", "sid")

    def __init__(self):
        self.prompt = self.answer = self.sid = None


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        calibrate: bool = False) -> dict:
    """With `calibrate`, the check's sample is drawn from wave 0 and the
    result is the program's `served_gap` and the control's."""
    tr, conf, params = cell.traffic, cell.config, cell.params
    fam = spec.family(conf)
    dm, cfg = fam.dims(conf), fam.program_config(conf)
    seeds = sub_seeds(seed)
    n_sess, turns, new = tr["sessions"], tr["turns"], tr["new_tokens"]
    lengths = prompt_lengths(tr)
    waves = 0 if calibrate else max(
        1, round(seconds / params["nominal_wave_s"]))
    log(f"{cell.name}: {cfg.n_layers} x {cfg.d_model}, heads "
        f"{cfg.n_heads}/{cfg.n_kv_heads} of {cfg.head_dim_}, vocab "
        f"{cfg.vocab}; {n_sess} sessions, prompts {lengths} + {new} new "
        f"tokens, {tr['num_pages']} pages of {tr['page_tokens']}; "
        f"{waves} waves in the window")
    rng = np.random.default_rng(seeds["data"])
    system = rng.integers(0, dm.vocab, tr["system_tokens"], dtype=np.int32)
    history = [rng.integers(0, dm.vocab, n - tr["system_tokens"],
                            dtype=np.int32) for n in lengths]
    user = rng.integers(0, dm.vocab, (waves + 2, n_sess, tr["user_tokens"]),
                        dtype=np.int32)
    orders = [admission_order(n_sess, turns, w, rng)
              for w in range(waves + 1)]
    spans = Spans()
    compiles = collections.Counter()
    win: dict = {}

    def on_compile(event, duration, **kw):
        if "compil" in event and "open" in win and "close" not in win:
            compiles[event] += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    with tempfile.TemporaryDirectory(prefix="chipbench_") as tmp, \
            contextlib.ExitStack() as stack:
        stack.callback(jax.monitoring.unregister_event_duration_listener,
                       on_compile)
        window = stack.enter_context(contextlib.ExitStack())
        params_ = nest(reference.init_params(fam, dm, seeds["init"]))
        eng = Engine(get_model(cfg), params_, EngineConfig(
            page_tokens=tr["page_tokens"], num_pages=tr["num_pages"]))
        sys_sid = eng.add(system, max_new=1)
        sessions = [Session() for _ in range(n_sess)]
        steps: List[tuple] = []          # (batch, context tokens) a step

        def serve_wave(w: int) -> List[dict]:
            """Admit one turn of every session, then decode them all."""
            submitted = time.perf_counter()
            wave = []
            for i in orders[w]:
                s, k = sessions[i], (i + w) % turns
                if w == 0:
                    parent = sys_sid
                    prompt = np.concatenate([system, history[k]])
                elif k == 0:
                    parent = sys_sid
                    prompt = np.concatenate([system, user[w, i]])
                else:
                    parent = s.sid
                    prompt = np.concatenate([s.prompt, np.asarray(
                        s.answer, np.int32), user[w, i]])
                with spans("engine.add"):
                    sid = eng.add(prompt, max_new=new, fork_from=parent)
                first = time.perf_counter()
                if s.sid is not None:
                    eng.release(s.sid)
                s.prompt, s.sid = prompt, sid
                wave.append({"sid": sid, "session": i, "turn": k,
                             "submitted": submitted, "first": first,
                             "prompt": prompt})
            sids = [t["sid"] for t in wave]
            while any(len(eng.result(x)) < new for x in sids):
                steps.append((len(sids), sum(eng.cache.seq_len[x] + 1
                                             for x in sids)))
                with spans("engine.step"):
                    eng.step()
            for t in wave:
                t["answer"] = list(eng.result(t["sid"]))
                sessions[t["session"]].answer = t["answer"]
            return wave

        served = serve_wave(0)
        # a turn that forks a previous turn mid-page, as the window's do
        i = next(i for i in range(n_sess) if 0 < (i + 1) % turns)
        s = sessions[i]
        sid = eng.add(np.concatenate([s.prompt, np.asarray(
            s.answer, np.int32), user[-1, i]]), max_new=1, fork_from=s.sid)
        eng.release(sid)
        jax.block_until_ready((eng.k_pool, eng.v_pool))
        del steps[:]
        shared0 = eng.cache.stats["pages_shared"]

        if trace:
            window.enter_context(device_trace.record(tmp + "/trace"))
        window.enter_context(span("window"))
        win["open"] = opened = time.perf_counter()
        turns_done: List[dict] = []
        for w in range(1, waves + 1):
            turns_done += serve_wave(w)
        win["close"] = closed = time.perf_counter()
        window.close()
        if compiles:
            log(f"compiles inside the window: {dict(compiles)}")
        summary = None
        if trace:
            summary = device_trace.summarize(device_trace.load(
                device_trace.trace_file(tmp + "/trace")))
        mem_peak = (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use")
        pages_shared = eng.cache.stats["pages_shared"] - shared0
        del eng, params_
        gc.collect()

    checked = turns_done if waves else served
    unfinished = sum(len(t["answer"]) != new for t in checked)
    outside = sum(not 0 <= x < dm.vocab for t in checked
                  for x in t["answer"])
    sample = _sample(checked, turns, params["checked_per_turn"],
                     seeds["shuffle"])
    t0 = time.perf_counter()
    gaps = _gaps(fam, dm, seeds["init"], sample, calibrate)
    log(f"reference over {len(sample)} turns "
        f"({sum(len(t['answer']) for t in sample)} served tokens) in "
        f"{time.perf_counter() - t0:.1f}s: {gaps}")
    if calibrate:
        return {part: {"served_gap": v} for part, v in gaps.items()}
    e2e = latency_metrics(turns_done, closed - opened)
    log(f"set-up {opened - t_start:.2f}s; window {closed - opened:.2f}s, "
        f"{len(turns_done)} turns, {len(steps)} decode steps; {e2e}")
    numbers = {"turns_unfinished": float(unfinished),
               "tokens_out_of_vocab": float(outside),
               "served_gap": gaps["program"]}
    dev = jax.devices()
    data = RunData(
        add_s=spans.durations("engine.add", opened),
        prompt_tokens=sum(len(t["prompt"]) for t in turns_done),
        pages_shared=pages_shared, page_tokens=tr["page_tokens"],
        steps=steps, dims=dm, family=fam, trace=summary)
    return {
        "numbers": numbers, "data": data,
        "e2e": {**e2e, "setup_s": opened - t_start},
        "device": {"platform": dev[0].platform, "kind": dev[0].device_kind,
                   "count": len(dev), "memory_peak_bytes": mem_peak},
        "attempted": len(turns_done),
        "failed": int(unfinished)}


def latency_metrics(turns: List[dict], window_s: float) -> dict:
    """The 95th percentile over every turn of its TTFT, from its wave's
    submission to its first token; and every token generated over the
    whole window."""
    ttft = [t["first"] - t["submitted"] for t in turns]
    return {"ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
            "decode_tokens_per_s":
                sum(len(t["answer"]) for t in turns) / window_s}


def _sample(turns_done: List[dict], turns: int, per_turn: int,
            seed: int) -> List[dict]:
    """`per_turn` turns of each turn index, drawn from the seed."""
    rng = np.random.default_rng(seed)
    out = []
    for k in range(turns):
        of_k = [t for t in turns_done if t["turn"] == k]
        pick = rng.choice(len(of_k), min(per_turn, len(of_k)), replace=False)
        out += [of_k[int(j)] for j in sorted(pick)]
    return out


def _wider(worst: float, gaps: jax.Array, n: int) -> float:
    """The wider of `worst` and the gaps from position n - 1 on; a gap
    that is not finite reads infinite."""
    g = np.asarray(gaps, np.float64)[n - 1:]
    return max(worst, float(g.max())) if np.isfinite(g).all() else math.inf


def _gaps(fam, dm, init_seed: int, sample: List[dict],
          control: bool) -> dict:
    """The widest gap of a served token below the reference's best, over
    the sampled turns; and the control's, where asked.  A turn with no
    token served is `turns_unfinished`'s."""
    p = reference.init_params(fam, dm, init_seed)
    worst = {"program": 0.0, "control": 0.0}
    for t in sample:
        n, answer = len(t["prompt"]), np.asarray(t["answer"], np.int32)
        if not len(answer):
            continue
        seq = np.concatenate([t["prompt"], answer[:-1]])
        served = np.zeros_like(seq)
        served[n - 1:] = answer
        g = reference.token_gaps(fam, p, jnp.asarray(seq), dm,
                                 jnp.asarray(served))
        worst["program"] = _wider(worst["program"], g, n)
        if control:
            g = reference.token_gaps(fam, p, jnp.asarray(seq), dm,
                                     jnp.asarray(served),
                                     round_to=CONTROL_DTYPE)
            worst["control"] = _wider(worst["control"], g, n)
    del p
    return worst if control else {"program": worst["program"]}
