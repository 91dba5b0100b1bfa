"""Model FLOPs of the prefill programs in the traced slice over their
device time times the chip's bf16 peak (percent).  FLOPs are the
algorithm's (``counts/<kind>.py``): live rows and real context only."""
from chipbench.readings import program_work


def read(run):
    w = program_work(run, "prefill")
    if w is None:
        return None
    flops, _, dev_s = w
    return 100.0 * flops / (dev_s * run.peaks["bf16_flops_per_s"])
