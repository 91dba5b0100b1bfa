"""Knee sweeps and correctness readings, many windows in one process.

  python3 chipbench/sweep.py --workload smollm-360m.chat \
      --rates 1,2,3,4 --seconds 30 --seed 11
  python3 chipbench/sweep.py --workload smollm-360m.chat \
      --seeds 1,2,3 --seconds 20 [--control 1]

The benchmark command runs one window per process.  This tool builds the
cell's engine once, warms it once, then drives one window per offered
rate (``--rates``: a knee sweep, the highest rate at which the backlog
does not grow through a window), or one window per seed at the cell's
own rate with the weights made anew from each seed (``--seeds``: the
correctness readings of the program, or with ``--control 1`` of the
float8 control put in its place).  Each window prints one JSON line.
Several ``--workload`` flags of one configuration share the engine.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
from chipbench import run as command  # noqa: E402  (path, cache dir)


def backlog(run) -> dict:
    """Whether the queue grew through the window: TTFT in each half of
    it, requests left at the close, and how long the drain took."""
    import numpy as np
    w = run.window
    half = w.seconds / 2
    first = [s.ttft for s in w.served if s.due < half and s.first]
    second = [s.ttft for s in w.served if s.due >= half and s.first]
    p = (lambda v: float(np.percentile(v, 90)) if v else None)
    left = sum(1 for s in w.served
               if s.last is None or s.last > w.seconds)
    return {"ttft_p90_first_half": p(first), "ttft_p90_second_half": p(second),
            "in_flight_at_close": left, "drain_s": w.drained - w.seconds}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", action="append", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--control", type=int, default=0)
    args = ap.parse_args()

    from chipbench import bench, harness
    specs = [bench.workload_spec(w) for w in args.workload]
    dev = command.start(args.workload[0], specs[0]["chips"])[0]
    setup = harness.build(specs[0], args.seed)
    harness.warm(setup, args.seed)
    print(json.dumps({"setup_s": time.perf_counter() - T_START,
                      "device": dev.device_kind}), flush=True)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    for spec in specs:
        jobs = ([(float(r), args.seed + i) for i, r in
                 enumerate(args.rates.split(","))] if args.rates else
                [(spec["cell"]["rate_rps"], s) for s in seeds])
        for rate, seed in jobs:
            sp = copy.deepcopy(spec)
            sp["cell"]["rate_rps"] = rate
            setup.spec = sp
            if seeds:
                harness.reseed(setup, seed)
            t = time.perf_counter()
            run = harness.measure(setup, seed, args.seconds, trace=False,
                                  t_start=t, device=dev)
            row = {"workload": spec["name"], "rate": rate, "seed": seed,
                   "attempted": len(run.window.served),
                   "compiles": run.compiles,
                   **{k: v["value"] for k, v in harness.read_metrics(
                       run, spec["end_to_end"] + spec["per_layer"]).items()
                      if k != "setup_s"},
                   **backlog(run), **harness.host_stalls(run)}
            if seeds:
                got = harness.correctness(setup, run, seed,
                                          control=bool(args.control))
                row.update({k: got[k] for k in got if k != "checks"},
                           control=bool(args.control))
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
