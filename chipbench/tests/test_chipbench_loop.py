"""The open loop stamps due, submit, row, first and last token times as
it should: exactly, against a scripted engine and clock, and consistently
on the paged engine at a reduced size on the CPU."""
from types import SimpleNamespace

import pytest

from chipbench import harness, loop
from chipbench.gen.openloop import Arrival
from chipbench.tests import tiny


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class ScriptedEngine:
    """One decode row; each step takes 0.1 s and gives the row 2 tokens."""

    def __init__(self, clock):
        self.clock = clock
        self.queue, self.rows, self.max_rows = [], [None], 1

    def submit(self, req):
        self.queue.append(req)

    def step(self):
        if self.rows[0] is None and self.queue:
            self.rows[0] = self.queue.pop(0)
            self.rows[0].t_admit = 1
        self.clock.t += 0.1
        req, done = self.rows[0], []
        if req is not None:
            req.out_tokens += [7, 7][:req.max_new_tokens
                                     - len(req.out_tokens)]
            if len(req.out_tokens) >= req.max_new_tokens:
                self.rows[0] = None
                done.append(req)
        return done


def make_request(s):
    return SimpleNamespace(out_tokens=[], max_new_tokens=s.max_new,
                           t_admit=None, error=None)


def test_stamps_against_a_scripted_engine():
    clock = Clock()
    eng = ScriptedEngine(clock)
    arrivals = [Arrival(0.0, [1], 4), Arrival(0.05, [1], 2)]
    w = loop.drive(eng, arrivals, 0.25, make_request=make_request,
                   clock=clock, sleep=clock.sleep)
    a, b = w.served
    assert a.submit == pytest.approx(0.0) and a.first == pytest.approx(0.1)
    assert a.last == pytest.approx(0.2) and a.n_out == 4
    assert a.ttft == pytest.approx(0.1)
    assert a.tpot == pytest.approx(0.1 / 3)
    # B falls due during A's first step, waits for the row
    assert b.submit == pytest.approx(0.1) and b.row == pytest.approx(0.3)
    assert b.ttft == pytest.approx(0.25) and b.tpot == pytest.approx(0.0)
    assert (a.n_out_window, b.n_out_window) == (4, 0)
    assert [s.rows for s in w.steps] == [1, 1, 1]
    assert w.drained == pytest.approx(0.3)


def test_idle_engine_waits_for_the_next_arrival():
    clock = Clock()
    eng = ScriptedEngine(clock)
    w = loop.drive(eng, [Arrival(1.0, [1], 2)], 2.0,
                   make_request=make_request, clock=clock, sleep=clock.sleep)
    (a,) = w.served
    assert a.submit == pytest.approx(1.0)
    assert a.ttft == pytest.approx(0.1)


@pytest.fixture(scope="module")
def window():
    spec = tiny.spec("llama", rate=6.0)
    setup = harness.build(spec, 2**31 + 17)
    harness.warm(setup, 3)
    run = harness.measure(setup, 2**31 + 17, 2.0, trace=False,
                          t_start=0.0)
    return setup, run


def test_paged_engine_window(window):
    setup, run = window
    w = run.window
    assert len(w.served) == 12
    assert run.compiles == 0                    # all shapes were warmed
    for s in w.served:
        assert s.finished and s.out == list(s.req.out_tokens)
        assert s.due <= s.submit <= s.row <= s.first <= s.last
        assert s.ttft == s.first - s.due
        assert s.tpot == pytest.approx((s.last - s.first) / (s.n_out - 1))
    total = sum(s.n_out for s in w.served)
    assert run.counters["tokens_generated"] == total
    assert sum(st.tokens for st in w.steps) == total
    m = harness.read_metrics(run, setup.spec["end_to_end"])
    assert m["out_tok_s"]["value"] == pytest.approx(
        sum(s.n_out_window for s in w.served) / 2.0)
    assert set(m) == {"ttft_p90_s", "tpot_p90_s", "out_tok_s", "setup_s"}
