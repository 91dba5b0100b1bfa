"""The engine's own phases in one cell: windows with the engine trace
(``serving/instrument.py``) off and on, many in one process.

  python3 chipbench/phases.py --workload smollm-360m.chat \
      --seeds 11,12,13 --seconds 51 [--slice-s 24]

The engine is built and warmed once.  Each seed then drives two windows
at the cell's rate over the same arrivals, in alternating order: one
with the trace off, as a ``--trace 0`` run, and one with it on and the
profiler recording the harness's slice mid-window, as a ``--trace 1``
run, or ``--slice-s`` seconds from the same start (so the readings
before it still compare).  Each window prints one JSON line:

- host clock, over the part of the window before the profiler's slice
  starts (in both windows, so they compare): the median engine step,
  ``tpot_p90_s`` and ``ttft_p90_s``;
- with the trace on, over the same part: ``sched.admit_wait_p90_s``
  (admit less submit, per request), ``engine.prefill_dispatch_p90_s``
  (prefilled less admit: the host's part of the prefill),
  ``engine.admit_to_first_p90_s`` (first token on the host less
  admit), ``engine.host_step_p50_s`` (``engine.step`` less its
  ``engine.sync``, per step); then ``compiles_by_program`` and the
  longest step's self time by span;
- from the profiled slice: the device idle by the innermost host span,
  engine spans included, over the whole slice and over the most idle
  part of it (``SUB_S`` seconds), and the device seconds of prefill and
  of decode in each step that admitted a request.

``harness.py`` does not attach the engine trace yet; this tool reads it
beside the benchmark until a ``benchmark`` change folds it in.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import List, Optional  # noqa: E402

if __name__ == "__main__":
    # as a command: the checkout on the path, then run.py's bootstrap
    # (the program's path, the checkout's compile cache) before JAX loads
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    import chipbench.run  # noqa: E402,F401
import jax  # noqa: E402

from chipbench import bench, harness, loop  # noqa: E402
from chipbench import trace as trace_mod  # noqa: E402
from chipbench.readings import p90  # noqa: E402

#: the length of the parts of a long slice compared for their idle
SUB_S = 3.0
#: the loop's own spans, which bound a slice
OUTER = ("step", "submit", "wait_arrival")


class Clock:
    """``time.perf_counter`` that keeps its first reading: the loop reads
    it first, as the window opens, so ``base`` turns the engine trace's
    stamps into the loop's window times."""

    def __init__(self):
        self.base: Optional[float] = None

    def __call__(self) -> float:
        t = time.perf_counter()
        if self.base is None:
            self.base = t
        return t


@dataclass
class Phased:
    window: loop.Window
    trace: object            # the EngineTrace, or None with the trace off
    base: float              # the window's opening on the trace's clock
    cut: float               # window time where the profiled slice starts
    rows: Optional[list] = None   # the profiled slice's events


def drive(setup: harness.Setup, seed: int, seconds: float, *,
          traced: bool, slice_s: Optional[float] = None) -> Phased:
    """One window at the cell's rate; ``traced`` attaches the engine
    trace and records the profiled slice (the harness's, or ``slice_s``
    seconds from the same start).  The engine must have been
    instrumented (``instrument(engine)``) once after warm-up."""
    from repro.serving.engine import Request
    from repro.serving.instrument import EngineTrace
    spec, eng = setup.spec, setup.engine
    arrivals = bench.generator(spec["traffic"]).schedule(
        spec["traffic"], spec["cell"]["rate_rps"], seconds, seed,
        setup.mcfg.vocab_size)
    tracer = harness.Tracer(seconds, setup.recorder)
    if slice_s:
        tracer.stop_s = min(seconds, tracer.start_s + slice_s)
    clock = Clock()
    tr = EngineTrace(clock).attach(eng) if traced else None
    try:
        window = loop.drive(
            eng, arrivals, seconds,
            make_request=lambda s: Request(id=s.id, prompt=list(s.prompt),
                                           max_new_tokens=s.max_new),
            drain_s=harness.DRAIN_S, trace=tracer if traced else None,
            span=jax.profiler.TraceAnnotation if traced else None,
            clock=clock)
    finally:
        if tr is not None:
            tr.close()
    out = Phased(window, tr, clock.base, tracer.start_s)
    if traced:
        out.rows = list(trace_mod.events(trace_mod.find_xplane(tracer.dir)))
    tracer.done()
    return out


def readings(ph: Phased) -> dict:
    """The host-clock and engine-trace readings of one window, over the
    part before the profiled slice."""
    w, cut = ph.window, ph.cut
    steps = [s.t1 - s.t0 for s in w.steps if s.t1 <= cut]
    out = {"attempted": len(w.served),
           "failed": sum(not s.finished for s in w.served),
           "cut_s": cut,
           "step_p50_s": statistics.median(steps) if steps else None,
           "tpot_p90_s": p90([s.tpot for s in w.served
                              if s.last is not None and s.last <= cut]),
           "ttft_p90_s": p90([s.ttft for s in w.served
                              if s.first is not None and s.first <= cut])}
    tr = ph.trace
    if tr is not None:
        until = ph.base + cut
        parts = tr.request_times(until)
        host = tr.host_step_times(until)
        out.update({
            "sched.admit_wait_p90_s": p90(parts["admit_wait"]),
            "engine.prefill_dispatch_p90_s": p90(parts["prefill_dispatch"]),
            "engine.admit_to_first_p90_s": p90(parts["admit_to_first"]),
            "engine.host_step_p50_s": (statistics.median(host)
                                       if host else None),
            "compiles_by_program": dict(tr.compiles),
            "longest_step": tr.longest_step()})
    return out


def sub_slices(rows, sub_s: float = SUB_S) -> List[list]:
    """``rows`` cut into runs of whole loop spans of about ``sub_s``
    seconds each: each part keeps the host events that start inside its
    loop spans and the device events that overlap them (the reduction
    clips those)."""
    host = [r for r in rows if r[0].startswith("/host:")]
    dev = [r for r in rows if not r[0].startswith("/host:")]
    outer = sorted((r[3], r[4]) for r in host if r[2] in OUTER)
    groups, lo = [], None
    for a, b in outer:
        if lo is None or a >= lo + sub_s:
            groups.append([a, b])
            lo = a
        else:
            groups[-1][1] = max(groups[-1][1], b)
    return [[r for r in host if a <= r[3] < b]
            + [r for r in dev if r[4] > a and r[3] < b] for a, b in groups]


def admitting_steps(rows) -> dict:
    """Device seconds of the prefill chunks that ran before each decode
    macro-step (a step that admitted a request runs them first), beside
    that step's decode program: medians and p90s over the steps that ran
    any prefill, and prefill's share of the two over the slice."""
    mods = sorted((a, b, trace_mod.program_of(name)) for p, line, name, a, b
                  in rows if p.startswith("/device:TPU:")
                  and "SparseCore" not in p and line == "XLA Modules")
    pre, prefill, decode, total = 0.0, [], [], {"prefill": 0.0,
                                                 "decode": 0.0}
    for a, b, prog in mods:
        if prog in total:
            total[prog] += b - a
        if prog == "prefill":
            pre += b - a
        elif prog == "decode":
            if pre > 0:
                prefill.append(pre)
                decode.append(b - a)
            pre = 0.0
    both = total["prefill"] + total["decode"]
    return {"steps": len(prefill),
            "prefill_s_p50": statistics.median(prefill) if prefill else None,
            "prefill_s_p90": p90(prefill),
            "decode_s_p50": statistics.median(decode) if decode else None,
            "prefill_share": total["prefill"] / both if both else None}


def idle_readings(summary: trace_mod.Summary) -> dict:
    idle = dict(summary.idle_by_span)
    total = sum(idle.values())
    return {"busy_s": summary.busy_s, "window_s": summary.window_s,
            "idle_by_span": summary.idle_by_span,
            "idle_unnamed_share": ((idle.get("step", 0.0)
                                    + idle.get("engine.step", 0.0)) / total
                                   if total > 0 else None)}


def device_readings(rows) -> dict:
    """The profiled slice's device idle by span, over the whole slice and
    over its most idle part, and the prefill of admitting steps."""
    from repro.serving.instrument import SPAN_NAMES
    names = harness.SPAN_NAMES + SPAN_NAMES
    out = idle_readings(trace_mod.reduce(rows, names))
    parts = [trace_mod.reduce(r, names) for r in sub_slices(rows)]
    out["sub_idle_shares"] = [p.idle_frac for p in parts]
    out["most_idle"] = idle_readings(max(parts, key=lambda p: p.idle_frac))
    out["admitting_steps"] = admitting_steps(rows)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--slice-s", type=float, default=None,
                    help="seconds profiled (default: the harness's slice)")
    args = ap.parse_args()

    from chipbench import run as command
    from repro.serving.instrument import instrument
    spec = bench.workload_spec(args.workload)
    dev = command.start(args.workload, spec["chips"])[0]
    seeds = [int(s) for s in args.seeds.split(",") if s]
    setup = harness.build(spec, seeds[0])
    harness.warm(setup, seeds[0])
    instrument(setup.engine)
    print(json.dumps({"setup_s": time.perf_counter() - T_START,
                      "device": dev.device_kind}), flush=True)
    for i, seed in enumerate(seeds):
        for on in ((False, True) if i % 2 == 0 else (True, False)):
            ph = drive(setup, seed, args.seconds, traced=on,
                       slice_s=args.slice_s)
            line = {"seed": seed, "trace": int(on), **readings(ph)}
            if ph.rows is not None:
                line.update(device_readings(ph.rows))
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
