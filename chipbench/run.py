"""The benchmark command: one run of one cell on the chip.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

One process: refuse any platform but a TPU (or fewer chips than the cell
asks for), keep JAX's compilation cache in the checkout, make the weights
on the device from the seed, build the paged engine, warm up the cell's
program shapes, drive the open loop for ``--seconds`` at the cell's fixed
rate, then compare a sample of what was served with the float32
reference.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (end-to-end metrics
with ``--trace 0``, per-layer ones from a profiled slice with
``--trace 1``), ``device`` and, last, ``checks``: each number compared
beside its limit, also printed as the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the cache lives in the checkout, whatever the machine sets
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def fail(msg: str, code: int = 2):
    print(f"chipbench: {msg}", file=sys.stderr)
    sys.exit(code)


def start(name: str, chips: int):
    """Find the chips and turn the compilation cache on; exit with no
    result where JAX finds no TPU, fewer chips than ``chips``, or a chip
    with no entry in the peaks table.  Returns JAX's devices."""
    import jax
    from chipbench import bench
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        fail(f"{name} needs {chips} chips, JAX found {len(devices)}")
    bench.peaks(devices[0].device_kind)
    from repro.launch.serve import setup_compile_cache
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return devices


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from chipbench import bench, harness
    spec = bench.workload_spec(args.workload)
    devices = start(args.workload, spec["chips"])
    out = harness.run_cell(spec, args.seed, args.seconds, bool(args.trace),
                           T_START, devices[0], len(devices))
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
