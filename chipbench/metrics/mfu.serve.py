"""Model FLOP/s utilization of the engine's decode step, in %: the model
FLOPs of the window's decode steps (the family's `forward_flops`, for
dense `dense.forward_flops`: the forward of one token a sequence against
its context, no padding) over the device time of the decode program (the
program that runs the paged-attention kernel) times the chip's bf16 peak
(`peaks.json`)."""

KERNEL = r'custom_call_target="tpu_custom_call"'


def read(run):
    seconds, _ = run.trace.seconds_of_programs_running(KERNEL)
    if not seconds or not run.steps or run.peaks is None:
        return None
    flops = sum(run.family.forward_flops(run.dims, b, ctx)
                for b, ctx in run.steps)
    return 100.0 * flops / (seconds * run.peaks["bf16_flops_per_s"])
