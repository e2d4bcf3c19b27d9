"""Mean seconds, over the window's resumes, that the restarted trainer
spent tracing, lowering, compiling or loading its programs from the
compile cache: the union of the intervals of the program's `jit.compile`
records at any depth under each `trainer.run` that is a root, the last
as many as the window's resumes.  Nothing where the program records
fewer."""
from chipbench import spans


def read(run):
    roots = spans.last("trainer.run", run.resumes)
    if roots is None:
        return None
    return sum(spans.trace.covered(spans.descendants(r, "jit.compile"), r)
               for r in roots) / len(roots)
