"""kernel.relation_roofline: the relation kernels' share of the HBM
bandwidth bound. The least bytes of the blocks the traced pass produced
(each block's tables read once and the block written once; see
``chipbench/roofline.py``) over the chip's HBM bandwidth, over the kernels'
device seconds, which cover the same launches. No operation bound: v5e
publishes no int32 vector rate."""

from chipbench import roofline


def read(run):
    t = run.read("kernel.relation_device_s")
    if not t:
        return None
    launch = run.launch
    least = roofline.least_bytes(launch["relations"], launch["produced"],
                                 launch["n_segments"], launch["rows"],
                                 launch["deg"])
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] / t
