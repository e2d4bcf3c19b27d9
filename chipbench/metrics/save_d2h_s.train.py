"""Mean seconds of the device-to-host copy of the train state in the
saves of the window: the program's span `trainer.save.d2h` under each
`trainer.save`, the last as many of them as the run's saves committed
(`AsyncCheckpointer.durations`).  Nothing where no save was made, or
where the program records no such span."""
from chipbench import spans


def read(run):
    saves = spans.last("trainer.save", len(run.save_commit_s), roots=False)
    return spans.mean_total(spans.below(saves, "trainer.save.d2h"))
