"""engine.t_sync_s: seconds per pass the consumer waited on in-flight
kernel results (``EngineStats.t_sync``, the paper's Fig. 10 wait)."""


def read(run):
    return run.per_pass("t_sync")
