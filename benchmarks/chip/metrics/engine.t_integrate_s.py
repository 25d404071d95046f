"""engine.t_integrate_s: seconds per pass spent copying each finished
launch to the host cache (``EngineStats.t_integrate``)."""


def read(run):
    return run.per_pass("t_integrate")
