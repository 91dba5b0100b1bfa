"""Share of the engine's decode rows that held a request, per
macro-step, weighted by the step's wall time, over the window's steps
(percent).  Traced runs read only the steps before the profiler
starts."""
from chipbench.readings import in_window


def read(run):
    steps = in_window(run)
    wall = sum(s.t1 - s.t0 for s in steps)
    if wall <= 0:
        return None
    busy = sum(s.rows * (s.t1 - s.t0) for s in steps)
    return 100.0 * busy / (run.max_rows * wall)
