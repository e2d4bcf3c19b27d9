"""Model FLOP/s utilization of the whole window: the model FLOPs of the
window's steps (the family's `train_flops_per_step`, for dense
`dense.train_flops_per_token`; no recomputation) over the traced window's
length times the chip's bf16 peak (`peaks.json`)."""


def read(run):
    if not run.window_steps or run.peaks is None:
        return None
    flops = run.window_steps * run.flops_per_step
    return 100.0 * flops / (run.trace.window_s
                            * run.peaks["bf16_flops_per_s"])
