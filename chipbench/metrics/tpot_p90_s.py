"""90th percentile over requests of each one's time per output token
after the first: (last token - first token) / (tokens - 1)."""
from chipbench.readings import p90


def read(run):
    return p90([s.tpot for s in run.window.served])
