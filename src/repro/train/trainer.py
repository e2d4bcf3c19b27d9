"""Host-level training loop: WTF data pipeline + transactional
checkpoint/restart + straggler & elastic hooks.

Fault-tolerance contract (what makes this runnable at 1000+ nodes):
  * The checkpoint manifest atomically carries BOTH the model/optimizer
    state and the data-pipeline cursor — a restarted job can never replay
    or skip data relative to the weights (WTF multi-file transaction).
  * Saves are asynchronous (AsyncCheckpointer) — data writes off the
    critical path, metadata commit at a step barrier.
  * `restore_or_init` makes restart the SAME code path as cold start: the
    trainer is a pure function of (config, filesystem state).
  * Elastic re-scale: `Trainer.with_hosts(n)` re-derives the pipeline for
    a new host count at the same global step (valid because epoch files
    are deterministic), and `CheckpointManager.reshard` re-partitions the
    checkpoint with zero data movement.
  * Straggler mitigation operates at the data layer: shards are handed
    out by deterministic assignment, and any host can serve any record
    range because slices are location-transparent — re-assignment costs
    one metadata read (see DESIGN.md §4).
"""
from __future__ import annotations

import contextlib
import functools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.checkpoint import AsyncCheckpointer, CheckpointManager
from repro.data.pipeline import DataPipeline, PipelineConfig, PipelineState
from repro.models import Model
from repro.parallel.sharding import make_rules, tree_shardings
from repro.util import trace

from . import optimizer as opt
from .step import TrainHyper, init_state, make_train_step


@dataclass
class TrainerConfig:
    total_steps: int = 100
    ckpt_every: int = 50
    keep_checkpoints: int = 3
    log_every: int = 10
    seed: int = 0
    pod_sync_every: int = 0        # >0: local-steps mode w/ pod averaging


class Trainer:
    def __init__(self, model: Model, pipeline: DataPipeline,
                 ckpt: CheckpointManager, hyper: TrainHyper = TrainHyper(),
                 cfg: TrainerConfig = TrainerConfig(),
                 rules=None, pod_sync: Optional[Callable] = None,
                 mesh=None):
        """Without a mesh, state and batches live on the default device.
        With one, the state is sharded by the model's schema under
        ``rules`` (derived from the mesh when not given) and each batch
        is split on its leading axis."""
        self.model = model
        self.pipeline = pipeline
        self.ckpt = ckpt
        # WtfClient is one-per-thread (it holds open-transaction state):
        # the async checkpoint thread gets its own client on the same
        # cluster, otherwise its commit transaction would interleave with
        # the main thread's data-pipeline reads
        async_mgr = CheckpointManager(ckpt.client.cluster.client(),
                                      ckpt.root, keep=ckpt.keep)
        self.async_ckpt = AsyncCheckpointer(async_mgr)
        self.cfg = cfg
        self.hyper = hyper
        self.pod_sync = pod_sync
        self.mesh = mesh
        if mesh is not None and rules is None:
            rules = make_rules(mesh, **dict(model.cfg.rules_overrides))
        self.rules = rules
        step = make_train_step(model, hyper, rules)
        self._init = functools.partial(init_state, model)
        if mesh is None:
            self.state_sharding = None
            self.train_step = jax.jit(step, donate_argnums=(0,))
        else:
            psh = tree_shardings(model.schema(), mesh, rules)
            rep = NamedSharding(mesh, PartitionSpec())
            self.state_sharding = {
                "params": psh, "opt": opt.OptState(m=psh, v=psh, count=rep),
                "step": rep}
            bsh = NamedSharding(mesh, PartitionSpec(rules["batch"]))
            self.train_step = jax.jit(
                step, in_shardings=(self.state_sharding, bsh),
                out_shardings=(self.state_sharding, None),
                donate_argnums=(0,))
        self.history: List[Dict[str, float]] = []

    # ------------------------------------------------------------ restart
    def restore_or_init(self):
        """Cold start or restart — one code path, transactional cursor."""
        key = jax.random.PRNGKey(self.cfg.seed)
        step = self.ckpt.latest_step()
        if step is None:
            init = (jax.jit(self._init) if self.mesh is None else jax.jit(
                self._init, out_shardings=self.state_sharding))
            return init(key), PipelineState()
        with trace.span("trainer.restore", step=step):
            man = self.ckpt.read_manifest(step)
            template = jax.eval_shape(self._init, key)
            state = self.ckpt.restore(template, step)
            pstate = PipelineState.from_dict(man.get("pipeline", {
                "epoch": 0, "step_in_epoch": 0}))
            with trace.span("trainer.device_put"):
                return jax.device_put(state, self.state_sharding), pstate

    # ---------------------------------------------------------------- run
    def run(self) -> Dict[str, Any]:
        """Train to ``total_steps``; returns the history and the final
        state."""
        with trace.span("trainer.run") as rec:
            state = self._run(rec)
        return {"final_step": self.cfg.total_steps,
                "history": self.history, "state": state}

    def _run(self, rec: trace.Record):
        state, pstate = self.restore_or_init()
        start = rec.attrs["step"] = int(state["step"])
        self.pipeline.state = pstate
        it = iter(self.pipeline)
        t_last = time.time()
        for step in range(start, self.cfg.total_steps):
            raw = next(it)
            batch = {"tokens": raw["tokens"], "labels": raw["labels"]}
            pstate = self.pipeline.state
            with jax.set_mesh(self.mesh) if self.mesh is not None \
                    else contextlib.nullcontext():
                state, metrics = self.train_step(state, batch)
            if self.pod_sync is not None and self.cfg.pod_sync_every \
                    and (step + 1) % self.cfg.pod_sync_every == 0:
                state["params"] = self.pod_sync(state["params"])
            if (step + 1) % self.cfg.log_every == 0 or step == start:
                m = {k: float(v) for k, v in metrics.items()}
                m["step"] = step + 1
                m["steps_per_s"] = self.cfg.log_every \
                    / max(time.time() - t_last, 1e-9)
                t_last = time.time()
                self.history.append(m)
                print(f"[train] step {step + 1}: loss={m['loss']:.4f} "
                      f"lr={m.get('lr', 0):.2e} "
                      f"({m['steps_per_s']:.2f} it/s)", flush=True)
            if (step + 1) % self.cfg.ckpt_every == 0:
                self._save(state, pstate, step + 1)
        self.async_ckpt.wait()
        return state

    def _save(self, state, pstate: PipelineState, step: int) -> None:
        """Copy the state to the host and hand it to the asynchronous
        checkpointer.  The copy waits for the queued steps to finish;
        ``trainer.save.drain`` shows that wait on its own."""
        with trace.span("trainer.save", step=step):
            with trace.span("trainer.save.drain"):
                jax.block_until_ready(state)
            with trace.span("trainer.save.d2h"):
                host_state = jax.tree.map(np.asarray, state)
            self.async_ckpt.save(step, host_state,
                                 extra={"pipeline": pstate.to_dict()},
                                 prev_step=self.ckpt.latest_step())

    # -------------------------------------------------------------- elastic
    def with_hosts(self, host_id: int, num_hosts: int) -> "Trainer":
        """Elastic re-scale: same global step, new host topology."""
        return Trainer(self.model, self.pipeline.with_hosts(host_id,
                                                            num_hosts),
                       self.ckpt, self.hyper, self.cfg, rules=self.rules,
                       mesh=self.mesh)
