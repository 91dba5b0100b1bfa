"""90th percentile of how late the loop submitted a request after its
due time (host clock).  Arrivals are submitted between engine steps, so
this is mostly the step in progress; a starved generator shows here, not
in the server's metrics.  Traced runs read only the part of the window
before the profiler starts."""
from chipbench.readings import p90, untraced_end


def read(run):
    end = untraced_end(run)
    return p90([s.submit - s.due for s in run.window.served
                if s.submit is not None and s.submit <= end])
