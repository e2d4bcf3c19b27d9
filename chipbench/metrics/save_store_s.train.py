"""Mean seconds, over the saves of the window, that the save spent in
WTF storage: the sum of the program's spans `ckpt.save.write` (one
shard's `writev`, opened and closed) and `ckpt.save.commit` (manifest,
`latest` and the transaction's commit) under the `ckpt.save` that each
`trainer.save` submitted, the last as many saves as the run committed.
Nothing where no save was made, or where the program records no such
span."""
from chipbench import spans


def read(run):
    saves = spans.last("trainer.save", len(run.save_commit_s), roots=False)
    return spans.mean_total(spans.below(
        saves, "ckpt.save", ("ckpt.save.write", "ckpt.save.commit")))
