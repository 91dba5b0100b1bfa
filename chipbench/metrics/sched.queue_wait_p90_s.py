"""90th percentile of the wait from a request's due time to the return
of the first step at which it held a decode row (admitted, prefilled).
Traced runs read only the part of the window before the profiler
starts."""
from chipbench.readings import p90, untraced_end


def read(run):
    end = untraced_end(run)
    return p90([s.row - s.due for s in run.window.served
                if s.row is not None and s.row <= end])
