"""Mean seconds, over the window's resumes, of fetching the checkpoint
from WTF: the program's span `ckpt.restore.fetch` (the manifest, the
opens, every shard's read issued and awaited) in each `trainer.run`
that is a root, the last as many as the window's resumes.  Nothing where
the program records fewer."""
from chipbench import spans


def read(run):
    return spans.mean_total(spans.below(
        spans.last("trainer.run", run.resumes),
        "trainer.restore", "ckpt.restore", "ckpt.restore.fetch"))
