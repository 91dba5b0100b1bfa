"""The engine trace read beside the benchmark (``phases.py``): request
marks split each TTFT into parts that add up to the loop's own stamp,
the readers do their arithmetic, the trace reduction labels device idle
by the engine's spans, and the dispatch counter's call log is the
harness recorder's."""
import glob
import os

import numpy as np
import pytest

from chipbench import bench, harness, loop, phases, trace
from chipbench.tests import tiny
from repro.serving.instrument import (SPAN_NAMES, EngineTrace, Mark,
                                      instrument)

NAMES = harness.SPAN_NAMES + SPAN_NAMES
SEED = 2**31 + 29


@pytest.fixture(scope="module")
def setup():
    spec = tiny.spec("llama", rate=6.0)
    setup = harness.build(spec, SEED)
    harness.warm(setup, 5)
    return setup, instrument(setup.engine)


@pytest.fixture(scope="module")
def windows(setup):
    setup, _ = setup
    on = phases.drive(setup, SEED, 2.0, traced=True)
    off = phases.drive(setup, SEED, 2.0, traced=False)
    return on, off


def test_ttft_splits_into_marks(windows):
    """For every request: (submit - due) + (admit - submit) + (prefilled
    - admit) + (first - prefilled) is the loop's TTFT, less what the
    engine did after the first token reached the host in that step (the
    rest of ``engine.deliver`` and ``engine.release``)."""
    ph, _ = windows
    tr, base = ph.trace, ph.base
    marks = tr.first_marks()
    kids = tr.children()
    roots = tr.roots()
    assert len(ph.window.served) == 12
    for s in ph.window.served:
        assert s.finished
        m = {k: v - base for k, v in marks[s.id].items()}
        parts = [m["submit"] - s.due, m["admit"] - m["submit"],
                 m["prefilled"] - m["admit"], m["first"] - m["prefilled"]]
        assert all(p >= 0 for p in parts)
        # the step whose return the loop stamped as this first token
        (step,) = [r for r in roots
                   if r.t0 - base <= m["first"] <= r.t1 - base]
        tail = {c.name: c for c in kids[step.id]}
        deliver, release = tail["engine.deliver"], tail["engine.release"]
        assert deliver.t0 - base <= m["first"] <= deliver.t1 - base
        gap = s.ttft - sum(parts)
        assert gap >= 0
        assert gap <= deliver.duration + release.duration + 0.01
        # the loop's submit stamp follows the engine's own mark
        assert 0 <= s.submit - m["submit"] < 0.01


def test_readings_of_a_warmed_window(windows):
    on, off = windows
    got = phases.readings(on)
    for name in ("sched.admit_wait_p90_s", "engine.prefill_dispatch_p90_s",
                 "engine.admit_to_first_p90_s", "engine.host_step_p50_s"):
        assert got[name] >= 0
    assert got["engine.prefill_dispatch_p90_s"] \
        <= got["engine.admit_to_first_p90_s"]
    assert got["compiles_by_program"] == {}
    longest = got["longest_step"]
    assert set(longest["phases"]) <= set(NAMES) | {"decode8", "decode4",
                                                   "decode2", "decode1"}
    assert sum(longest["phases"].values()) == pytest.approx(longest["s"])
    # the trace records nothing it was not asked to: the off window
    # reads the host clock alone, on the same arrivals
    plain = phases.readings(off)
    assert "engine.host_step_p50_s" not in plain
    assert plain["attempted"] == got["attempted"] == 12
    assert plain["failed"] == got["failed"] == 0


def test_engine_readers_on_a_hand_made_trace():
    """``readings`` takes each engine number over what ended before the
    profiled slice: p90 over requests, the median over steps."""
    base = 100.0
    times = iter([100.0, 100.5, 100.9, 101.0,       # step 1
                  102.0, 102.2, 102.8, 103.0,       # step 2
                  104.0, 104.1, 104.2, 104.3])      # after the cut
    tr = EngineTrace(times.__next__)
    try:
        for _ in range(3):
            with tr.span("engine.step"):
                with tr.span("engine.sync"):
                    pass
    finally:
        tr.close()
    for rid, (sub, adm, pre, first) in enumerate(
            [(100.0, 100.1, 100.2, 100.6), (100.2, 101.2, 101.5, 102.0),
             (103.5, 104.0, 104.1, 104.2)]):
        for event, t in (("submit", sub), ("admit", adm),
                         ("prefilled", pre), ("first", first)):
            tr.marks.append(Mark(rid, event, t))
    window = loop.Window(51.0, [], [], 51.0)
    got = phases.readings(phases.Phased(window, tr, base, cut=3.5))
    assert got["sched.admit_wait_p90_s"] == pytest.approx(
        0.1 + 0.9 * (1.0 - 0.1))
    assert got["engine.prefill_dispatch_p90_s"] == pytest.approx(
        0.1 + 0.9 * (0.3 - 0.1))
    assert got["engine.admit_to_first_p90_s"] == pytest.approx(
        0.5 + 0.9 * (0.8 - 0.5))
    assert got["engine.host_step_p50_s"] == pytest.approx((0.6 + 0.4) / 2)
    assert got["longest_step"]["phases"] == pytest.approx(
        {"engine.step": 0.6, "engine.sync": 0.4})
    assert got["compiles_by_program"] == {}


DEV = "/device:TPU:0"


def test_reduce_labels_idle_by_engine_spans():
    host = [("/host:CPU", "main", "step", 0.0, 1.0),
            ("/host:CPU", "main", "engine.step", 0.01, 0.99),
            ("/host:CPU", "main", "engine.admit", 0.02, 0.2),
            ("/host:CPU", "main", "engine.prefill_prep", 0.05, 0.15),
            ("/host:CPU", "main", "prefill", 0.1, 0.12),
            ("/host:CPU", "main", "engine.decode_prep", 0.3, 0.5),
            ("/host:CPU", "main", "decode16", 0.45, 0.48),
            ("/host:CPU", "main", "engine.sync", 0.5, 0.9),
            ("/host:CPU", "main", "engine.deliver", 0.9, 0.95)]
    dev = [(DEV, "XLA Modules", "jit_paged_prefill_chunk(1)", 0.12, 0.3),
           (DEV, "XLA Modules", "jit_decode_steps(2)", 0.48, 0.9),
           (DEV, "XLA Ops", "fusion.1", 0.12, 0.3),
           (DEV, "XLA Ops", "fusion.2", 0.48, 0.9)]
    s = trace.reduce(host + dev, NAMES)
    assert s.program_s == pytest.approx({"prefill": 0.18, "decode": 0.42})
    assert dict(s.idle_by_span) == pytest.approx({
        "step": 0.02,                     # [0, .01], [.99, 1]
        "engine.step": 0.01 + 0.04,       # [.01, .02], [.95, .99]
        "engine.admit": 0.03,             # [.02, .05]
        "engine.prefill_prep": 0.05,      # [.05, .1]; [.12, .15] is busy
        "prefill": 0.02,                  # [.1, .12]
        "engine.decode_prep": 0.15,       # [.3, .45]
        "decode16": 0.03,                 # [.45, .48]
        "engine.deliver": 0.05})          # [.9, .95]


CHIP = sorted(glob.glob(str(bench.HERE / "testdata" / "*.xplane.pb.gz")))
ENGINE_CHIP = [p for p in CHIP if ".engine." in os.path.basename(p)]


@pytest.mark.parametrize("path", ENGINE_CHIP, ids=os.path.basename)
def test_engine_spans_name_the_idle_of_a_chip_trace(path):
    """A slice recorded on a v5e (``phases.py --keep-trace``) with the
    engine trace on: its device idle falls under named spans, and no
    more than a tenth stays under the loop's ``step`` or
    ``engine.step``'s own time."""
    s = trace.reduce(list(trace.events(path)), NAMES)
    idle = dict(s.idle_by_span)
    total = sum(idle.values())
    assert total == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
    assert any(k.startswith("engine.") for k in idle)
    assert (idle.get("step", 0) + idle.get("engine.step", 0)) <= 0.1 * total


def test_sub_slices_and_admitting_steps():
    """A long slice is cut at loop spans into parts of about ``SUB_S``
    seconds; each step's prefill device time is what ran before its
    decode program."""
    host = [("/host:CPU", "main", "step", 0.0, 2.0),
            ("/host:CPU", "main", "engine.step", 0.0, 2.0),
            ("/host:CPU", "main", "step", 2.0, 3.5),
            ("/host:CPU", "main", "engine.step", 2.0, 3.5),
            ("/host:CPU", "main", "wait_arrival", 3.5, 4.0),
            ("/host:CPU", "main", "step", 4.0, 5.0)]
    mods = [(DEV, "XLA Modules", "jit_paged_prefill_chunk(1)", 0.1, 0.3),
            (DEV, "XLA Modules", "jit_paged_prefill_chunk(1)", 0.3, 0.4),
            (DEV, "XLA Modules", "jit_decode_steps(2)", 0.4, 1.9),
            (DEV, "XLA Modules", "jit_decode_steps(2)", 2.0, 3.0),
            (DEV, "XLA Modules", "jit_paged_prefill_chunk(1)", 4.0, 4.1),
            (DEV, "XLA Modules", "jit_decode_steps(2)", 4.1, 4.9)]
    ops = [(DEV, "XLA Ops", "fusion", a, b) for _, _, _, a, b in mods]
    parts = phases.sub_slices(host + mods + ops)
    # [0, 3.5] (the step at 2.0 starts inside the first 3 s), [3.5, 5]
    assert [sorted(r[2] for r in p if r[0].startswith("/host"))
            for p in parts] == [
        ["engine.step", "engine.step", "step", "step"],
        ["step", "wait_arrival"]]
    s = [trace.reduce(p, NAMES) for p in parts]
    assert [x.window_s for x in s] == pytest.approx([3.5, 1.5])
    assert [x.idle_frac for x in s] == pytest.approx([
        1 - (0.3 + 1.5 + 1.0) / 3.5, 1 - 0.9 / 1.5])
    got = phases.admitting_steps(host + mods + ops)
    assert got["steps"] == 2
    assert got["prefill_s_p50"] == pytest.approx((0.3 + 0.1) / 2)
    assert got["decode_s_p50"] == pytest.approx((1.5 + 0.8) / 2)
    assert got["prefill_share"] == pytest.approx(0.4 / (0.4 + 3.3))


@pytest.mark.parametrize("path", ENGINE_CHIP, ids=os.path.basename)
def test_device_readings_of_a_chip_trace(path):
    """The engine-spanned v5e slice: its parts' idle shares bracket the
    whole slice's, and its admitting steps ran prefill on the device."""
    got = phases.device_readings(list(trace.events(path)))
    whole = 1 - got["busy_s"] / got["window_s"]
    shares = got["sub_idle_shares"]
    assert min(shares) <= whole + 1e-9 and max(shares) >= whole - 1e-9
    most = got["most_idle"]
    assert 1 - most["busy_s"] / most["window_s"] == pytest.approx(
        max(shares))
    steps = got["admitting_steps"]
    assert steps["steps"] >= 1 and 0 < steps["prefill_share"] < 1


def _sized(calls):
    """Per-call sizes as the harness reads them, made comparable."""
    return [(name, traced, {k: np.asarray(v).tolist()
                            for k, v in info.items()})
            for name, traced, info in calls]


@pytest.mark.parametrize("tracing", [False, True])
def test_call_log_is_the_recorders(setup, tracing):
    """The dispatch counter's log holds what the harness's ``Recorder``
    logs, so the roofline and MFU readers get the same sizes from it."""
    setup, counts = setup
    rec = setup.recorder
    counts.calls, counts.tracing = [], tracing
    rec.calls, rec.tracing = [], tracing
    try:
        run = harness.measure(setup, SEED + 1, 1.0, trace=False,
                              t_start=0.0)
        got = harness._calls(counts)
    finally:
        counts.calls, counts.tracing, rec.tracing = None, False, False
    assert any(name.startswith("decode") for name, _, _ in run.calls)
    assert any(name == "prefill" for name, _, _ in run.calls)
    assert _sized(got) == _sized(run.calls)
    assert {traced for _, traced, _ in got} == {tracing}
