"""Production train driver.

  python -m repro.launch.train --arch smollm-360m --steps 200 [--smoke]

Runs one process with the full substrate: the WTF data pipeline,
transactional async checkpoints and restart.  Without ``--smoke`` the
model has its published widths; on a TPU the remat policy is the fastest
whose compiled step fits the chip's memory (`fit_remat`), so
``smollm-360m`` trains on one v5e.  ``--smoke`` selects the reduced
same-family config that CPU tests use.  `chip_smoke.py` at the repository
root drives this path on the chip and checks its results.  At exit it
prints, for each span of the program, the count and the total, self and
longest seconds.
"""
from __future__ import annotations

import argparse
import tempfile

import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.core import Cluster
from repro.data.pipeline import DataPipeline, PipelineConfig
from repro.data.records import write_token_shard
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model
from repro.train import AdamWConfig, TrainHyper, fit_remat
from repro.train.trainer import Trainer, TrainerConfig
from repro.util import trace


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="smollm-360m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU tests)")
    ap.add_argument("--data-dir", default=None)
    ap.add_argument("--accum", type=int, default=1)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch)).replace(max_seq=args.seq)
    hyper = TrainHyper(adamw=AdamWConfig(warmup_steps=20,
                                         decay_steps=args.steps),
                       accum_steps=args.accum)
    model = fit_remat(get_model(cfg), hyper, args.batch, args.seq)
    data_dir = args.data_dir or tempfile.mkdtemp(prefix="wtf_launch_")
    cluster = Cluster(n_servers=4, data_dir=data_dir, replication=2)
    fs = cluster.client()
    if not fs.exists("/corpus"):
        fs.mkdir("/corpus")
        rng = np.random.RandomState(0)
        write_token_shard(
            fs, "/corpus/shard0",
            iter(rng.randint(0, cfg.vocab,
                             args.batch * (args.seq + 1) * 64)),
            args.seq + 1)
    pipe = DataPipeline(fs, PipelineConfig(
        src_paths=("/corpus/shard0",), work_dir="/epochs",
        block_tokens=args.seq + 1, global_batch=args.batch, seed=0))
    trainer = Trainer(
        model, pipe, CheckpointManager(fs, "/ckpt", keep=3),
        hyper=hyper, cfg=TrainerConfig(total_steps=args.steps))
    trainer.run()
    cluster.close()
    for name, row in trace.summary().items():
        print(f"[trace] {name}: {row}")


if __name__ == "__main__":
    main()
