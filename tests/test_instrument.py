"""The engine trace (serving/instrument.py): spans nest and time as
they should under a scripted clock, the null trace records nothing, a
traced engine serves exactly what an untraced one serves, and compiles
land in the span of the program that compiled."""
import itertools
import time

import jax
import pytest
from jax._src import compilation_cache

from repro.configs import get_smoke_config
from repro.serving import PagedServingEngine, Request, ServingEngine
from repro.serving.instrument import (MARKS, NULL_TRACE, SPAN_NAMES,
                                      EngineTrace, Mark, instrument)
from repro.serving.testbed import FakeEngine


class Clock:
    """Reads 0, 1, 2, ...: every read advances one second."""

    def __init__(self):
        self.reads = itertools.count()

    def __call__(self):
        return float(next(self.reads))


@pytest.fixture
def trace():
    tr = EngineTrace(Clock())
    yield tr
    tr.close()


def test_spans_nest_with_parent_ids(trace):
    with trace.span("engine.step"):                 # t0 = 0
        with trace.span("engine.admit"):            # 1
            with trace.span("engine.prefill_prep"):  # 2
                with trace.span("prefill"):         # 3, 4
                    pass
            # prefill_prep ends at 5, admit at 6
        with trace.span("engine.sync"):             # 7, 8
            pass
    # step ends at 9
    step, admit, prep, prog, sync = trace.spans
    assert [s.id for s in trace.spans] == [0, 1, 2, 3, 4]
    assert [s.parent for s in trace.spans] == [-1, 0, 1, 2, 0]
    assert (step.t0, step.t1) == (0.0, 9.0)
    assert (prep.t0, prep.t1) == (2.0, 5.0) and (prog.t0, prog.t1) == (3, 4)
    assert (admit.t0, admit.t1, sync.t0, sync.t1) == (1, 6, 7, 8)
    assert trace.roots() == [step]
    assert [s.id for s in trace.children()[0]] == [1, 4]


def test_self_time_is_duration_less_children(trace):
    with trace.span("engine.step"):                 # 0 .. 9
        with trace.span("engine.admit"):            # 1 .. 6
            with trace.span("engine.prefill_prep"):  # 2 .. 5
                with trace.span("prefill"):         # 3 .. 4
                    pass
        with trace.span("engine.sync"):             # 7 .. 8
            pass
    (step,) = trace.roots()
    got = trace.phases(step)
    assert got == {"engine.step": 9 - 5 - 1, "engine.admit": 5 - 3,
                   "engine.prefill_prep": 3 - 1, "prefill": 1,
                   "engine.sync": 1}
    assert sum(got.values()) == step.duration


def test_marks_keep_the_first_of_each_event(trace):
    trace.mark(3, "submit")
    trace.mark(3, "admit")
    trace.mark(3, "prefilled")
    trace.mark(3, "admit")
    assert trace.first_marks() == {3: {"submit": 0.0, "admit": 1.0,
                                       "prefilled": 2.0}}


def test_null_trace_records_nothing():
    a, b = NULL_TRACE.span("engine.step"), NULL_TRACE.span("x")
    assert a is b                                   # one shared context
    with a:
        assert NULL_TRACE.mark(1, "submit") is None
    eng = FakeEngine(decode_steps=4)
    assert eng.trace is NULL_TRACE
    counts = instrument(eng)
    eng.submit(Request(id=0, prompt=[1, 2, 3], max_new_tokens=6))
    eng.run()
    assert eng.trace is NULL_TRACE and vars(NULL_TRACE) == {}
    assert counts.total_dispatches == 0             # FakeEngine jits none


ENGINES = {
    "paged": lambda cfg: PagedServingEngine(
        cfg, max_rows=2, max_len=64, block_size=8, num_blocks=7,
        prefill_chunk=4, decode_steps=8),
    "dense": lambda cfg: ServingEngine(cfg, max_batch=2, cache_len=64,
                                       prefill_chunk=4, decode_steps=4),
    "fake-speculative": lambda cfg: FakeEngine(
        cfg, max_rows=2, max_len=64, block_size=8, num_blocks=7,
        speculative=3),
}


def serve(make, trace):
    """Four requests through two rows, the last two submitted mid-run;
    the paged pool is small enough to preempt."""
    eng = make(get_smoke_config("smollm-360m"))
    counts = instrument(eng)
    if trace is not None:
        trace.attach(eng)
    reqs = [Request(id=i, prompt=list(range(1 + i, 12 + 3 * i)),
                    max_new_tokens=10 + 3 * i) for i in range(4)]
    for r in reqs[:2]:
        eng.submit(r)
    eng.step()
    for r in reqs[2:]:
        eng.submit(r)
    eng.run()
    stamps = {r.id: (r.out_tokens, r.t_submit, r.t_admit, r.t_first,
                     r.t_done, r.n_preempted, r.error) for r in reqs}
    return eng, counts, stamps


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_traced_engine_serves_the_same(kind):
    """The step-clock contract: with a trace on, every token and every
    ``Request.t_*`` stamp is what the untraced engine gives."""
    _, _, want = serve(ENGINES[kind], None)
    trace = EngineTrace(time.perf_counter)
    try:
        eng, counts, got = serve(ENGINES[kind], trace)
    finally:
        trace.close()
    assert got == want
    assert eng.trace is NULL_TRACE                  # close detached it
    names = [s.name for s in trace.spans]
    assert set(names) - set(counts.counts) <= set(SPAN_NAMES)
    # one root per step, one sync span per host sync, one program span
    # per dispatch, every span closed and inside its parent
    assert all(s.t1 is not None for s in trace.spans)
    assert names.count("engine.sync") == eng.n_host_syncs > 0
    for prog, n in counts.counts.items():
        assert names.count(prog) == n
    by_id = {s.id: s for s in trace.spans}
    for s in trace.spans:
        if s.parent >= 0:
            p = by_id[s.parent]
            assert p.t0 <= s.t0 <= s.t1 <= p.t1
        else:
            assert s.name == "engine.step"
    # every request's life in order, on the trace's clock
    # (a preempted request is admitted and prefilled again: its first
    # marks still come in this order)
    marks = trace.first_marks()
    assert set(marks) == set(want)
    for rid, m in marks.items():
        assert [m[e] for e in MARKS] == sorted(m.values())
    preps = [s for s in trace.spans if s.name == "engine.prefill_prep"]
    assert preps and all(by_id[s.parent].name == "engine.admit"
                         for s in preps)
    if kind == "paged":
        assert eng.n_preemptions > 0


def _use_compile_cache(on: bool):
    # jax decides once whether it uses the persistent cache: reset that
    jax.config.update("jax_enable_compilation_cache", on)
    compilation_cache.reset_cache()


def test_new_decode_program_compiles_in_its_span():
    prev = jax.config.jax_enable_compilation_cache
    _use_compile_cache(False)
    try:
        eng = PagedServingEngine(get_smoke_config("smollm-360m"),
                                 max_rows=2, max_len=64, block_size=8,
                                 prefill_chunk=4, decode_steps=8)
        eng.submit(Request(id=0, prompt=list(range(1, 10)),
                           max_new_tokens=16))
        eng.run()                                   # decode8 only
        counts = instrument(eng)
        trace = EngineTrace(time.perf_counter).attach(eng)
        try:
            eng.submit(Request(id=1, prompt=list(range(3, 12)),
                               max_new_tokens=4))
            eng.run()                               # one decode4 call
        finally:
            trace.close()
    finally:
        _use_compile_cache(prev)
    assert counts.counts["decode4"] == 1
    assert trace.compiles["decode4"] >= 1
    assert set(trace.compiles) == {"decode4"}
    (span,) = [s for s in trace.spans if s.name == "decode4"]
    assert trace.spans[span.parent].name == "engine.decode_prep"


def test_request_and_step_readers():
    # three steps, each with a sync; the clock reads in order of entry
    # and exit
    tr = EngineTrace(iter([10.0, 10.5, 10.9, 11.0,          # step 1
                           12.0, 12.2, 12.8, 13.0,          # step 2
                           14.0, 14.1, 14.2, 14.3]).__next__)
    try:
        for _ in range(3):
            with tr.span("engine.step"):
                with tr.span("engine.sync"):
                    pass
    finally:
        tr.close()
    # step durations 1.0, 1.0, 0.3; syncs 0.4, 0.6, 0.1
    assert tr.host_step_times(13.5) == pytest.approx([0.6, 0.4])
    assert tr.longest_step()["s"] == pytest.approx(1.0)
    assert tr.longest_step()["phases"] == pytest.approx(
        {"engine.step": 0.6, "engine.sync": 0.4})
    for rid, life in enumerate([(10.0, 10.1, 10.2, 10.6),
                                (10.2, 11.2, 11.5, 12.0),
                                (13.5, 14.0, 14.1, 14.2)]):
        tr.marks += [Mark(rid, e, t) for e, t in zip(MARKS, life)]
    tr.marks.append(Mark(3, "submit", 13.0))       # never admitted
    got = tr.request_times(13.5)
    assert got == {"admit_wait": pytest.approx([0.1, 1.0]),
                   "prefill_dispatch": pytest.approx([0.1, 0.3]),
                   "admit_to_first": pytest.approx([0.5, 0.8])}
    assert [len(v) for v in tr.request_times(20.0).values()] == [3, 3, 3]
    empty = EngineTrace(time.perf_counter)
    empty.close()
    assert empty.longest_step() == {}


def test_kv_block_counts_of_paged_decode():
    """A paged ``decode{k}`` call counts the attn-table blocks each live
    row's decode attention reads over its k iterations, against every
    row's whole table; the free row reads none, and a dense engine
    counts nothing."""
    cfg = get_smoke_config("smollm-360m")
    eng = PagedServingEngine(cfg, max_rows=2, max_len=64, block_size=8,
                             prefill_chunk=4, decode_steps=8)
    counts = instrument(eng)
    # 9 prompt tokens: prefill writes 8, the first decode is at pos 8,
    # and 4 tokens make one decode4 call (slots 9..12: 2 blocks each)
    eng.submit(Request(id=0, prompt=list(range(1, 10)), max_new_tokens=4))
    eng.run()
    assert counts.counts["decode4"] == 1 and counts.decode_dispatches == 1
    assert counts.kv_blocks_live == 4 * 2
    assert counts.kv_blocks_table == 4 * 2 * (64 // 8)
    dense = ServingEngine(cfg, max_batch=2, cache_len=64, prefill_chunk=4,
                          decode_steps=4)
    counts = instrument(dense)
    dense.submit(Request(id=0, prompt=list(range(1, 10)), max_new_tokens=4))
    dense.run()
    assert counts.decode_dispatches == 1
    assert counts.kv_blocks_live == counts.kv_blocks_table == 0
