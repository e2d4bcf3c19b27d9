"""On-chip benchmark of WTF's main path on a TPU: the trainer's input
pipeline, asynchronous checkpoints and steps, its restart from a
checkpoint, and the serving engine's paged KV cache.

`python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>` runs one cell of `BENCHMARK.json` once and prints one JSON
line.  Every configuration, traffic mix, per-cell limit and per-layer
metric is a file of its own under this directory, found by its name.
"""
