"""Mean seconds, over the saves of the window, that the save spent
turning leaves into bytes and their digests: the sum of the program's
spans `ckpt.save.encode` (`leaf_to_bytes`, one a leaf) under the
`ckpt.save` that each `trainer.save` submitted, the last as many saves
as the run committed.  Nothing where no save was made, or where the
program records no such span."""
from chipbench import spans


def read(run):
    saves = spans.last("trainer.save", len(run.save_commit_s), roots=False)
    return spans.mean_total(
        spans.below(saves, "ckpt.save", "ckpt.save.encode"))
