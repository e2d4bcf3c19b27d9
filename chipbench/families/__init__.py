"""One module per model family, found by the name a configuration file
gives under `program.family` (`spec.family`).  A family module is the
only place that knows a model's shape; the drivers, the smoke cells, the
readers and the generic reference (`reference.py`) reach the model
through it alone.  It exposes:

* `dims(conf)`: a hashable record of the sizes the reference and the
  work counts need, with `vocab` among them (the traffic draws its token
  ids below it);
* `program_config(conf)`: the program's `ModelConfig` for the file;
* `leaf_specs(dims)`: name -> (shape, init) of every weight, for the
  weight recipe of `reference.init_params`; stacked layer leaves are
  named `layers/...`;
* `row_logits(p, tokens, dims, round_to)`: next-token logits [S, V] of
  one sequence; `row_loss(p, tokens, labels, dims, round_to)`: its loss,
  with any auxiliary terms, and a dict of per-row statistics;
* `after_update(p, stats, dims)`: the weights after a step's update of
  the leaves no gradient moves, from the step's summed statistics, and
  `non_gradient_leaves(dims)`: those leaves, which AdamW leaves alone;
* `train_flops_per_step(dims, batch, seq, step_metrics)`: model FLOPs of
  one training step, which may read the metrics dicts the program's step
  returned in the window; a family that serves also gives
  `forward_flops` and `paged_attention_bytes` (as in `dense.py`);
* `TINY`: the configuration keys its smoke cell replaces, and
  optionally `SMOKE_LIMITS`, by driver, in place of `smoke.py`'s.

The reference half of a family imports nothing of the program; only
`program_config` reaches it.
"""
