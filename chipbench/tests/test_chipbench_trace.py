"""The trace reduction: busy union, idle share, program time, top
operations and idle gaps by host span, on hand-made events and on a
trace recorded on a v5e chip (``testdata/``)."""
import glob

import pytest

from chipbench import bench, harness, trace

DEV = "/device:TPU:0"


def rows():
    host = [("/host:CPU", "main", "step", 0.0, 1.0),
            ("/host:CPU", "main", "prefill", 0.1, 0.15),
            ("/host:CPU", "main", "decode16", 0.2, 0.25),
            ("/host:CPU", "main", "wait_arrival", 1.0, 1.5),
            ("/host:CPU", "main", "step", 1.5, 2.0)]
    mods = [(DEV, "XLA Modules", "jit_paged_prefill_chunk(1)", 0.1, 0.3),
            (DEV, "XLA Modules", "jit_decode_steps(2)", 0.4, 0.9),
            (DEV, "XLA Modules", "jit_decode_steps(2)", 1.6, 1.9)]
    ops = [(DEV, "XLA Ops", "fusion.1", 0.1, 0.2),
           (DEV, "XLA Ops", "fusion.2", 0.15, 0.3),
           (DEV, "XLA Ops", "fusion.3", 0.4, 0.9),
           (DEV, "XLA Ops", "fusion.3", 1.6, 1.9),
           (DEV, "XLA Ops", "outside", 2.5, 3.0)]
    return host + mods + ops


def test_reduce_hand_made_events():
    s = trace.reduce(rows(), harness.SPAN_NAMES)
    assert s.window_s == pytest.approx(2.0)
    assert s.busy_s == pytest.approx(0.2 + 0.5 + 0.3)
    assert s.idle_frac == pytest.approx(0.5)
    assert s.program_s == pytest.approx({"prefill": 0.2, "decode": 0.8})
    assert s.program_calls == {"prefill": 1, "decode": 2}
    assert s.top_ops[0] == ("decode:fusion.3", pytest.approx(0.8))
    idle = dict(s.idle_by_span)
    # idle [0, .1] in step, [.3, .4] and [.9, 1] in step, [1, 1.5]
    # waiting for an arrival, [1.5, 1.6] and [1.9, 2] in step
    assert idle == pytest.approx({"step": 0.5, "wait_arrival": 0.5})


def test_union_and_gaps():
    u = trace.union([(0, 1), (0.5, 2), (3, 4)])
    assert u == [(0, 2), (3, 4)]
    assert trace.gaps(u, -1, 5) == [(-1, 0), (2, 3), (4, 5)]


def test_no_host_spans_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce([r for r in rows() if r[0] == DEV], ("step",))


CHIP = sorted(glob.glob(str(bench.HERE / "testdata" / "*.xplane.pb.gz")))


@pytest.mark.parametrize("path", CHIP)
def test_trace_recorded_on_the_chip(path):
    s = trace.reduce(list(trace.events(path)), harness.SPAN_NAMES)
    assert s.devices == 1
    assert 0 < s.busy_s < s.window_s
    assert s.program_calls["decode"] > 0 and s.program_calls["prefill"] > 0
    assert 0 < s.program_s["decode"] + s.program_s["prefill"] <= s.window_s
    assert s.top_ops[0][0].split(":")[0] in ("decode", "prefill")
    assert sum(v for _, v in s.idle_by_span) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)


def test_timeline_labels_the_innermost_span():
    spans = [(0.0, 1.0, "step"), (0.2, 0.4, "prefill"), (2.0, 3.0, "x")]
    assert trace.timeline(spans) == [
        (0.0, 0.2, "step"), (0.2, 0.4, "prefill"), (0.4, 1.0, "step"),
        (1.0, 2.0, "none"), (2.0, 3.0, "x")]
    got = trace.attribute([(0.1, 0.3), (0.9, 2.5), (3.5, 4.0)],
                          trace.timeline(spans))
    assert got == pytest.approx({"step": 0.2, "prefill": 0.1, "none": 1.5,
                                 "x": 0.5})
