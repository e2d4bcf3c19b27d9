"""Mean time a window step waited on `DataPipeline`'s `next()`, in ms
(the benchmark's span around each call; host clock)."""


def read(run):
    waits = run.pipeline_wait_s
    return 1e3 * sum(waits) / len(waits) if waits else None
