"""90th percentile of time to first token, from each request's due time,
over every request due in the window.  A request that never served a
token counts with the time from its due time to the end of the drain."""
from chipbench.readings import p90


def read(run):
    w = run.window
    return p90([s.ttft if s.first is not None else w.drained - s.due
                for s in w.served])
