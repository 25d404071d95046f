"""completion.device_s: device seconds of the cross-segment completion
programs in the traced pass (``kernels/completion_gather.py``)."""

PROGRAMS = ("_resolve_jit", "_gather_candidates_xla", "_gather_union_xla",
            "_union_jit")


def read(run):
    if run.trace is None:
        return None
    return run.trace.seconds_of(PROGRAMS)
