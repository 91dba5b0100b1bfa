"""The metric readers' arithmetic, on a hand-made run."""
import numpy as np
import pytest

from chipbench import bench, harness, loop, trace
from chipbench.counts import llama
from chipbench.tests import tiny

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}


def run(traced=True):
    spec = tiny.spec("llama")
    m = spec["config"]["model"]
    served = [loop.Served(0, 0.0, [1] * 8, 3, submit=0.01, row=0.2,
                          first=0.3, last=0.5, n_out=3, n_out_window=3),
              loop.Served(1, 1.0, [1] * 8, 2, submit=1.1, row=1.2,
                          first=1.4, last=2.5, n_out=2, n_out_window=1)]
    steps = [loop.Step(0.0, 0.5, 2, 3), loop.Step(0.5, 2.0, 1, 1),
             loop.Step(2.0, 2.5, 1, 1)]
    w = loop.Window(2.0, served, steps, 2.5)
    calls = [("decode4", traced, {"pos": np.array([5, 9]),
                                  "budget": np.array([4, 1]), "k": 4}),
             ("prefill", traced, {"c": 8, "p0": 0})]
    s = trace.Summary(window_s=2.0, busy_s=1.5, devices=1,
                      program_s={"decode": 0.5, "prefill": 0.25},
                      program_calls={"decode": 1, "prefill": 1})
    r = harness.Run(spec, w, calls, {"prefix_tokens_hit": 16,
                                     "prefill_tokens": 48}, {
        "peak_bytes_in_use": 3e9, "peak_bytes_reserved": 1e9}, 4, 9.0,
        trace=s, peaks=PEAKS)
    return r, m


def read(name, r):
    return bench.metric_reader(name).read(r)


def test_host_clock_and_counter_readers():
    r, _ = run()
    assert read("ttft_p90_s", r) == pytest.approx(np.percentile([.3, .4], 90))
    assert read("tpot_p90_s", r) == pytest.approx(
        np.percentile([0.1, 1.1], 90))
    assert read("out_tok_s", r) == pytest.approx(4 / 2.0)
    assert read("setup_s", r) == 9.0
    assert read("gen.lag_p90_s", r) == pytest.approx(
        np.percentile([0.01, 0.1], 90))
    assert read("sched.queue_wait_p90_s", r) == pytest.approx(
        np.percentile([0.2, 0.2], 90))
    # steps ending inside the 2 s window: 2 rows x 0.5 s + 1 row x 1.5 s
    assert read("sched.rows_busy", r) == pytest.approx(
        100 * (2 * 0.5 + 1 * 1.5) / (4 * 2.0))
    assert read("kv.prefix_hit", r) == pytest.approx(25.0)
    assert read("device.hbm_peak_gb", r) == pytest.approx(4.0)
    assert read("device.idle", r) == pytest.approx(25.0)


def test_device_trace_readers():
    r, m = run()
    f, b = 0.0, 0.0
    for j, live in enumerate([[6, 10], [7], [8], [9]]):
        fj, bj = llama.decode_iteration(m, live)
        f, b = f + fj, b + bj
    least = max(f / 1e12, b / 1e9)
    assert read("decode_roofline", r) == pytest.approx(100 * least / 0.5)
    assert read("decode.mfu", r) == pytest.approx(100 * f / (0.5 * 1e12))
    pf, pb = llama.prefill_chunk(m, 8, 0)
    assert read("prefill_roofline", r) == pytest.approx(
        100 * max(pf / 1e12, pb / 1e9) / 0.25)


def test_readers_without_a_trace_find_nothing():
    r, _ = run(traced=False)
    for name in ("decode_roofline", "decode.mfu", "prefill_roofline",
                 "prefill.mfu"):
        assert read(name, r) is None
    r.trace = None
    assert read("device.idle", r) is None


def test_traced_runs_read_host_metrics_before_the_profiler():
    r, _ = run()
    r.window.trace_span = (0.05, 0.5)
    assert read("gen.lag_p90_s", r) == pytest.approx(0.01)
    assert read("sched.queue_wait_p90_s", r) is None
    assert read("sched.rows_busy", r) is None
