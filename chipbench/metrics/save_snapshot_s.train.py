"""Mean seconds of the host snapshot of every leaf in the saves of the
window: the program's span `ckpt.submit` (`AsyncCheckpointer.save`) less
its `ckpt.wait` (the wait for the previous save), under each
`trainer.save`, the last as many of them as the run's saves committed.
Nothing where no save was made, or where the program records no such
span."""
from chipbench import spans


def read(run):
    saves = spans.last("trainer.save", len(run.save_commit_s), roots=False)
    submits = spans.below(saves, "ckpt.submit")
    if submits is None:
        return None
    return sum(sum(spans.less(g, "ckpt.wait")) for g in submits) / \
        len(submits)
