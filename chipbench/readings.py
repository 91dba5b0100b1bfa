"""Arithmetic shared by the metric readers (``metrics/*.py``)."""
from __future__ import annotations

import sys
from typing import Optional, Tuple

import numpy as np

from chipbench import bench


def p90(values) -> Optional[float]:
    values = [v for v in values if v is not None]
    return float(np.percentile(values, 90)) if values else None


def untraced_end(run) -> float:
    """Where the host-clock readings stop: the window's close, or the
    profiler's start in a traced run (starting and stopping it stalls
    the loop for seconds, which is the profiler's time, not the
    server's)."""
    w = run.window
    return w.seconds if w.trace_span is None else min(w.seconds,
                                                      w.trace_span[0])


def in_window(run):
    """Steps that ended inside the window, before any profiling."""
    end = untraced_end(run)
    return [s for s in run.window.steps if s.t1 <= end]


def program_work(run, prog: str) -> Optional[Tuple[float, float, float]]:
    """(FLOPs, least seconds, device seconds) of program ``prog``
    (``decode`` or ``prefill``) over the traced slice, or None where the
    slice ran none of it.  The least time of a call is the larger of its
    FLOPs over the peak and its bytes over the HBM bandwidth, with the
    FLOPs and bytes the algorithm needs (``counts/<kind>.py``)."""
    if run.trace is None or run.peaks is None:
        return None
    dev_s = run.trace.program_s.get(prog, 0.0)
    config = run.spec["config"]
    m = config["model"]
    counts = bench.counts(config)
    peak, bw = run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"]
    flops = least = 0.0
    n = 0
    for name, traced, info in run.calls:
        if not traced or not name.startswith(prog):
            continue
        n += 1
        if prog == "decode":
            f = b = 0.0
            for j in range(info["k"]):
                live = info["budget"] > j
                if live.any():
                    fj, bj = counts.decode_iteration(
                        m, info["pos"][live] + j + 1)
                    f, b = f + fj, b + bj
        else:
            f, b = counts.prefill_chunk(m, info["c"], info["p0"])
        flops += f
        least += max(f / peak, b / bw)
    if n == 0 or dev_s <= 0:
        return None
    seen = run.trace.program_calls.get(prog, 0)
    if seen != n:
        print(f"chipbench: {prog}: {n} calls on the host, {seen} programs "
              "on the device in the traced slice", file=sys.stderr)
    return flops, least, dev_s
