"""Mean seconds, over the window's resumes, of decoding the fetched
checkpoint to host arrays: the program's span `ckpt.restore.decode`
(shards joined, `bytes_to_leaf`, `unflatten_tree`) in each `trainer.run`
that is a root, the last as many as the window's resumes.  Nothing where
the program records fewer."""
from chipbench import spans


def read(run):
    return spans.mean_total(spans.below(
        spans.last("trainer.run", run.resumes),
        "trainer.restore", "ckpt.restore", "ckpt.restore.decode"))
