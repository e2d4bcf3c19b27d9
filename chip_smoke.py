#!/usr/bin/env python3
"""Drive the main path once on a TPU, at smollm-360m's published widths.

  python chip_smoke.py              # one chip: train, resume, serve
  python chip_smoke.py --chips 4    # four chips: sharded train, 4-host
                                    # save, reshard resume, vs one chip
  python chip_smoke.py --rehearse   # CPU, smoke config, interpret-mode
                                    # kernels (add --chips 4 with
                                    # XLA_FLAGS=--xla_force_host_platform_device_count=4)

Phases (one process; data, weights and prompts come from --seed):

* device   prints platform, device_kind and count; anything but a TPU
           fails unless --rehearse was given.
* train    a 4-server, replication-2 WTF cluster in a temporary data dir
           holds a token corpus; `Trainer.run` reads it through
           `DataPipeline` and saves asynchronously through
           `CheckpointManager` inside the window.  The remat policy is
           the fastest whose compiled step fits the chip (`fit_remat`).
* resume   a second `Trainer` restores the saved step bit-identically,
           with the pipeline cursor of the manifest, and takes more steps.
* serve    `Engine` decodes requests, some forked from a shared prefix,
           through the compiled Pallas paged-attention kernel; one float32
           request must match the ring-cache reference decode.

Any failed check raises, so the process exits non-zero; only a run in
which every phase passed prints the final JSON line.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.checkpoint import CheckpointManager  # noqa: E402
from repro.checkpoint.serialize import flatten_tree  # noqa: E402
from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.core import Cluster  # noqa: E402
from repro.data.pipeline import DataPipeline, PipelineConfig  # noqa: E402
from repro.data.records import write_token_shard  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.serving.engine import Engine, EngineConfig  # noqa: E402
from repro.train import AdamWConfig, TrainHyper, fit_remat  # noqa: E402
from repro.train.trainer import Trainer, TrainerConfig  # noqa: E402

ARCH = "smollm-360m"
# train: batch x seq per step; the launcher's default batch of 8
TRAIN_BATCH, TRAIN_SEQ = 8, 1024
REHEARSE_BATCH, REHEARSE_SEQ = 4, 64
TRAIN_STEPS, CKPT_EVERY, RESUME_STEPS = 6, 3, 2
SHARDED_STEPS = 3                  # --chips 4: steps before the save
CORPUS_RECORDS = 16 * TRAIN_BATCH  # two steps' worth more than any run
# serve: 4 prompts + 4 forks of the first = a decode batch of 8 whose
# page tables stay 5 pages wide, so decode compiles once
PAGE_TOKENS, NUM_PAGES = 16, 2048
PROMPT_LEN, FORK_SUFFIX, MAX_NEW = 48, 16, 16
N_PROMPTS, N_FORKS = 4, 4
SERVE_BATCH = N_PROMPTS + N_FORKS
SERVE_TABLE_PAGES = -(-(PROMPT_LEN + FORK_SUFFIX + MAX_NEW - 1)
                      // PAGE_TOKENS)
# one optimizer setting for the remat search and the trainers, so the
# search's compile of the chosen step is the one the trainers load
HYPER = TrainHyper(adamw=AdamWConfig(warmup_steps=2, decay_steps=100))
NEAR_TIE = 1e-3        # float32 logits: a token flip only within this gap
LOSS_RTOL = 1e-2       # sharded vs one-chip losses: ~2.5 bf16 ulps


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {msg}")


def device_phase(args) -> dict:
    devs = jax.devices()
    d = devs[0]
    dev = {"platform": d.platform, "kind": d.device_kind,
           "count": len(devs)}
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)} backend={jax.default_backend()}", flush=True)
    if not args.rehearse and d.platform != "tpu":
        raise SystemExit(f"no TPU found (platform {d.platform!r}); "
                         "pass --rehearse for the CPU rehearsal")
    if len(devs) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} "
                         f"devices, found {len(devs)}")
    return dev


# ------------------------------------------------------------------ train
def _sizes(args):
    return ((REHEARSE_BATCH, REHEARSE_SEQ) if args.rehearse
            else (TRAIN_BATCH, TRAIN_SEQ))


def _write_corpus(fs, vocab: int, seq: int, seed: int) -> None:
    fs.mkdir("/corpus")
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, vocab, CORPUS_RECORDS * (seq + 1))
    write_token_shard(fs, "/corpus/shard0", iter(toks.tolist()), seq + 1)


def _trainer(model, fs, root: str, batch: int, seq: int, steps: int,
             ckpt_every: int, seed: int, mesh=None) -> Trainer:
    # prefetch=0: the saved cursor is then exactly the steps consumed
    pipe = DataPipeline(fs, PipelineConfig(
        src_paths=("/corpus/shard0",), work_dir="/epochs",
        block_tokens=seq + 1, global_batch=batch, seed=seed, prefetch=0))
    return Trainer(model, pipe, CheckpointManager(fs, root, keep=3),
                   hyper=HYPER,
                   cfg=TrainerConfig(total_steps=steps,
                                     ckpt_every=ckpt_every, log_every=1,
                                     seed=seed),
                   mesh=mesh)


def _losses(history) -> list:
    losses = [h["loss"] for h in history]
    _check(all(math.isfinite(x) for x in losses), f"finite losses {losses}")
    return losses


def _step_times(history) -> list:
    return [round(1.0 / h["steps_per_s"], 4) for h in history]


def _host_copy(state) -> dict:
    return flatten_tree(jax.tree.map(np.asarray, state))


def _bit_identical(restored, saved: dict) -> int:
    got = _host_copy(restored)
    _check(got.keys() == saved.keys(), "restored leaf names")
    for name, a in saved.items():
        b = got[name]
        _check(a.dtype == b.dtype and a.shape == b.shape
               and a.tobytes() == b.tobytes(),
               f"leaf {name} restored bit-identically")
    return len(saved)


def train_phase(args, cluster, cfg):
    batch, seq = _sizes(args)
    fs = cluster.client()
    _write_corpus(fs, cfg.vocab, seq, args.seed)
    t0 = time.perf_counter()
    model = fit_remat(get_model(cfg), HYPER, batch, seq)
    print(f"[train] {cfg.name}: {cfg.n_layers} layers x d_model "
          f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, head_dim "
          f"{cfg.head_dim_}, vocab {cfg.vocab}; batch {batch} x seq {seq}; "
          f"remat={model.cfg.remat} (chosen in "
          f"{time.perf_counter() - t0:.1f}s)", flush=True)

    trainer = _trainer(model, fs, "/ckpt", batch, seq, TRAIN_STEPS,
                       CKPT_EVERY, args.seed)
    t0 = time.perf_counter()
    out = trainer.run()
    wall = time.perf_counter() - t0
    losses = _losses(out["history"])
    _check(len(losses) == TRAIN_STEPS, "one loss per step")
    saves = trainer.async_ckpt.durations
    _check(len(saves) == TRAIN_STEPS // CKPT_EVERY,
           f"{TRAIN_STEPS // CKPT_EVERY} async saves, got {len(saves)}")
    print(f"[train] losses {losses}", flush=True)
    print(f"[train] step times (s, to the loss on the host; the first "
          f"includes the compile-cache load) {_step_times(out['history'])}",
          flush=True)
    print(f"[train] async saves (s, submission to commit) "
          f"{[round(s, 2) for s in saves]}; run wall {wall:.1f}s",
          flush=True)
    saved = _host_copy(out["state"])
    nbytes = sum(a.nbytes for a in saved.values())
    print(f"[train] state {nbytes / 1e9:.2f} GB in {len(saved)} leaves",
          flush=True)
    return model, saved


def resume_phase(args, cluster, model, saved: dict) -> None:
    batch, seq = _sizes(args)
    fs = cluster.client()
    trainer = _trainer(model, fs, "/ckpt", batch, seq,
                       TRAIN_STEPS + RESUME_STEPS, CKPT_EVERY, args.seed)
    man = trainer.ckpt.read_manifest()
    _check(man["step"] == TRAIN_STEPS, f"latest step {man['step']}")
    t0 = time.perf_counter()
    state, pstate = trainer.restore_or_init()
    jax.block_until_ready(state)
    restore_s = time.perf_counter() - t0
    _check(int(state["step"]) == TRAIN_STEPS, "restored step")
    n = _bit_identical(state, saved)
    _check(pstate.to_dict() == man["pipeline"]
           == {"epoch": 0, "step_in_epoch": TRAIN_STEPS},
           f"cursor {pstate.to_dict()} vs manifest {man['pipeline']}")
    del state
    print(f"[resume] restored step {TRAIN_STEPS} in {restore_s:.2f}s "
          f"(fetch + device_put); {n} leaves bit-identical; cursor "
          f"{man['pipeline']}", flush=True)
    out = trainer.run()
    losses = _losses(out["history"])
    _check(len(losses) == RESUME_STEPS
           and out["history"][-1]["step"] == TRAIN_STEPS + RESUME_STEPS,
           "steps after resume")
    print(f"[resume] losses after resume {losses}; step times "
          f"{_step_times(out['history'])}", flush=True)


# ------------------------------------------------------------------ serve
def _reference_decode(model, params, prompt, max_new):
    """Greedy decode on the model's ring-buffer cache (the decode step
    that `make_serve_step` wraps); returns the tokens and each
    generated position's logits."""
    cache = model.init_cache(1, max_len=len(prompt) + max_new)
    step = jax.jit(model.decode_step)
    toks, logits = [], []
    for t in range(len(prompt) + max_new - 1):
        cur = prompt[t] if t < len(prompt) else toks[-1]
        lg, cache = step(params, cache,
                         {"tokens": jnp.asarray([[cur]], jnp.int32),
                          "pos": jnp.asarray([t], jnp.int32)})
        if t >= len(prompt) - 1:
            row = np.asarray(lg[0, -1], np.float32)
            logits.append(row)
            toks.append(int(row.argmax()))
    return toks[:max_new], logits[:max_new]


def _run_to_end(eng, sids) -> int:
    steps = 0
    while not all(eng._requests[s].done for s in sids):
        eng.step()
        steps += 1
    return steps


def serve_phase(args, cfg) -> None:
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    eng = Engine(model, params, EngineConfig(page_tokens=PAGE_TOKENS,
                                             num_pages=NUM_PAGES))
    on_tpu = jax.default_backend() == "tpu"
    _check(eng.kernel_interpret != on_tpu,
           "paged attention compiled on a TPU, interpreted elsewhere")
    rng = np.random.default_rng(args.seed + 1)
    prompts = [rng.integers(0, cfg.vocab, PROMPT_LEN).astype(np.int32)
               for _ in range(N_PROMPTS)]
    t0 = time.perf_counter()
    sids = [eng.add(p, max_new=MAX_NEW) for p in prompts]
    for _ in range(N_FORKS):
        suffix = rng.integers(0, cfg.vocab, FORK_SUFFIX).astype(np.int32)
        sids.append(eng.add(np.concatenate([prompts[0], suffix]),
                            max_new=MAX_NEW, fork_from=sids[0]))
    prefill_s = time.perf_counter() - t0
    shared = eng.cache.stats["pages_shared"]
    _check(shared == N_FORKS * PROMPT_LEN // PAGE_TOKENS,
           f"forks share the prefix pages ({shared})")

    hlo = eng._decode.lower(
        eng.params, eng.k_pool, eng.v_pool,
        jax.ShapeDtypeStruct((SERVE_BATCH, 1), jnp.int32),
        jax.ShapeDtypeStruct((SERVE_BATCH,), jnp.int32),
        jax.ShapeDtypeStruct((SERVE_BATCH, SERVE_TABLE_PAGES),
                             jnp.int32)).as_text()
    _check(("tpu_custom_call" in hlo) == on_tpu,
           "decode lowers the paged-attention kernel to a TPU custom call")

    t0 = time.perf_counter()
    steps = _run_to_end(eng, sids)
    decode_s = time.perf_counter() - t0
    _check(eng._decode._cache_size() == 1, "decode compiled once")
    outs = [eng.result(s) for s in sids]
    _check(all(len(o) == MAX_NEW and all(0 <= t < cfg.vocab for t in o)
               for o in outs), "every request finished in-vocab")
    print(f"[serve] {len(sids)} requests ({N_FORKS} forked from a "
          f"{PROMPT_LEN}-token prefix, {shared} pages shared), "
          f"{MAX_NEW} tokens each, {steps} decode steps; "
          f"kernel={'compiled' if not eng.kernel_interpret else 'interpret'}"
          f"; prefill {prefill_s:.1f}s, decode {decode_s:.1f}s (both "
          f"include compiles)", flush=True)
    del eng

    # float32 request against the ring-cache reference, every matmul at
    # full float32 precision on both sides (the TPU's default rounds
    # float32 operands to bfloat16)
    m32 = get_model(cfg.replace(compute_dtype="float32"))
    with jax.default_matmul_precision("highest"):
        eng = Engine(m32, params, EngineConfig(page_tokens=PAGE_TOKENS,
                                               num_pages=64))
        sid = eng.add(prompts[1], max_new=MAX_NEW)
        _run_to_end(eng, [sid])
        got = eng.result(sid)
        ref, ref_logits = _reference_decode(m32, params, list(prompts[1]),
                                            MAX_NEW)
    agree = next((i for i, (a, b) in enumerate(zip(got, ref)) if a != b),
                 MAX_NEW)
    if agree < MAX_NEW:
        lg = ref_logits[agree]
        gap = float(lg[ref[agree]] - lg[got[agree]])
        _check(gap <= NEAR_TIE,
               f"float32 tokens diverge at {agree}: {got} vs {ref} "
               f"(logit gap {gap:.3g} > {NEAR_TIE})")
        print(f"[serve] float32 request: first {agree} tokens equal the "
              f"reference; token {agree} is a near tie (logit gap "
              f"{gap:.3g} <= {NEAR_TIE})", flush=True)
    else:
        print(f"[serve] float32 request: all {MAX_NEW} greedy tokens equal "
              f"the ring-cache reference {ref}", flush=True)


# ---------------------------------------------------------------- 4 chips
def sharded_phase(args, cluster, cfg) -> None:
    """Train on a (data=2, model=2) mesh, save as 4 hosts, reshard to one
    shard and resume on one chip; the same steps on one chip are the
    reference."""
    batch, seq = _sizes(args)
    fs = cluster.client()
    _write_corpus(fs, cfg.vocab, seq, args.seed)
    model = fit_remat(get_model(cfg), HYPER, batch, seq)
    total = SHARDED_STEPS + RESUME_STEPS
    never = total + 1
    print(f"[sharded] {cfg.name}: {cfg.n_layers} x {cfg.d_model}, vocab "
          f"{cfg.vocab}; batch {batch} x seq {seq}; remat={model.cfg.remat}",
          flush=True)

    ref = _trainer(model, fs, "/ckpt_one", batch, seq, total, never,
                   args.seed).run()
    ref_losses = _losses(ref["history"])
    del ref
    print(f"[sharded] one chip: losses {ref_losses}", flush=True)

    mesh = make_host_mesh(data=2, model=2)
    trainer = _trainer(model, fs, "/ckpt_mesh", batch, seq, SHARDED_STEPS,
                       never, args.seed, mesh=mesh)
    out = trainer.run()
    losses = _losses(out["history"])
    print(f"[sharded] mesh {dict(mesh.shape)}: losses {losses}; step times "
          f"{_step_times(out['history'])}", flush=True)
    _check(np.allclose(losses, ref_losses[:SHARDED_STEPS], rtol=LOSS_RTOL,
                       atol=0), f"sharded losses within rtol {LOSS_RTOL}")

    saved = _host_copy(out["state"])
    del out
    mgr = trainer.ckpt
    t0 = time.perf_counter()
    for host in reversed(range(4)):        # host 0 commits last
        mgr.save(SHARDED_STEPS, saved, host_id=host, num_hosts=4,
                 extra={"pipeline": trainer.pipeline.state.to_dict()})
    save_s = time.perf_counter() - t0
    man = mgr.read_manifest(SHARDED_STEPS)
    _check(max(m["shards"] for m in man["leaves"].values()) == 4,
           "big leaves saved in 4 shards")
    # the one-shard copy needs a step directory of its own
    one_shard = SHARDED_STEPS + 1
    t0 = time.perf_counter()
    mgr.reshard(SHARDED_STEPS, new_shards=1, dst_step=one_shard)
    reshard_s = time.perf_counter() - t0

    resumed = _trainer(model, fs, "/ckpt_mesh", batch, seq, total, never,
                       args.seed)
    t0 = time.perf_counter()
    state, pstate = resumed.restore_or_init()
    jax.block_until_ready(state)
    restore_s = time.perf_counter() - t0
    _check(resumed.ckpt.latest_step() == one_shard, "resumes the reshard")
    n = _bit_identical(state, saved)
    _check(pstate.to_dict() == {"epoch": 0, "step_in_epoch": SHARDED_STEPS},
           f"cursor {pstate.to_dict()}")
    del state
    print(f"[sharded] 4-host save {save_s:.1f}s, reshard to 1 shard "
          f"{reshard_s:.2f}s, restore on one chip {restore_s:.1f}s; {n} "
          f"leaves bit-identical", flush=True)
    after = _losses(resumed.run()["history"])
    print(f"[sharded] one chip after resume: losses {after} vs one-chip "
          f"run {ref_losses[SHARDED_STEPS:]}", flush=True)
    _check(np.allclose(after, ref_losses[SHARDED_STEPS:], rtol=LOSS_RTOL,
                       atol=0), f"resumed losses within rtol {LOSS_RTOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU at smoke size, Pallas in interpret mode")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    dev = device_phase(args)
    print(f"[device] compile cache {enable_compile_cache()}", flush=True)
    cfg = (get_smoke_config(ARCH) if args.rehearse else get_config(ARCH))
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as data_dir:
        cluster = Cluster(n_servers=4, data_dir=data_dir, replication=2)
        try:
            if args.chips == 4:
                sharded_phase(args, cluster, cfg)
            else:
                model, saved = train_phase(args, cluster, cfg)
                resume_phase(args, cluster, model, saved)
                del saved
                serve_phase(args, cfg)
        finally:
            cluster.close()
    print(f"[done] all phases passed in {time.perf_counter() - t0:.1f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
