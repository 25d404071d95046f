"""device.idle_share: share of the traced pass in which no operation ran
on the device, from the profiler trace (1 - busy / window)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
