"""Seconds the training loop stalled in the iteration that saved, once
the device had finished the queued steps: the host copy of the state and
the save's submission, during which the device has no step to run.  From
the times the loop asked the pipeline for each batch (the benchmark's
wrapper; host clock), as `drivers/train.py`'s `loop_stall` works it out.
Nothing where no save was made in the window."""


def read(run):
    return run.save_stall_s
