"""Roofline share of the prefill programs in the traced slice: the least
time the chip needs for their work (the larger of FLOPs over the peak and
bytes over the HBM bandwidth, per call) over their device time
(percent).  No Pallas kernel is on the path, so the whole program is the
kernel."""
from chipbench.readings import program_work


def read(run):
    w = program_work(run, "prefill")
    if w is None:
        return None
    _, least, dev_s = w
    return 100.0 * least / dev_s
