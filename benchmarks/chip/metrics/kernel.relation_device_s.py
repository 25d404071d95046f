"""kernel.relation_device_s: device seconds of the relation kernels in the
traced pass, summed over the programs named below (``kernels/ops.py``)."""

PROGRAMS = ("_relation_block_fused",)


def read(run):
    if run.trace is None:
        return None
    return run.trace.seconds_of(PROGRAMS)
