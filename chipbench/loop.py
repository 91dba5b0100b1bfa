"""The open loop: submit requests when they fall due, step the engine,
stamp wall times.

``drive`` knows nothing of devices.  It takes any engine with
``submit(Request)``, ``step()`` (finished requests), ``queue``,
``rows`` (one entry per decode row, ``None`` when free) and
``max_rows``, so the tests run it on the CPU at a reduced size.

All times are host seconds after the window opens.  A request's TTFT
counts from its *due* time, so a late submit or a queue is charged to
it.  A request's tokens are stamped with the return of the step that
delivered them: the step ends in the engine's one host sync, so the
tokens are on the host by then.
"""
from __future__ import annotations

import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Callable, List, Optional


@dataclass
class Served:
    """One request of the window, with its host stamps."""
    id: int
    due: float
    prompt: List[int]
    max_new: int
    submit: Optional[float] = None
    row: Optional[float] = None    # first step return holding a row
    first: Optional[float] = None  # step return with its first token
    last: Optional[float] = None   # step return with its last token
    n_out: int = 0
    n_out_window: int = 0          # tokens delivered before the close
    out: List[int] = field(default_factory=list)
    req: object = None             # the engine's Request

    @property
    def finished(self) -> bool:
        return self.n_out >= self.max_new

    @property
    def ttft(self) -> Optional[float]:
        return None if self.first is None else self.first - self.due

    @property
    def tpot(self) -> Optional[float]:
        if self.first is None or self.n_out < 2:
            return None
        return (self.last - self.first) / (self.n_out - 1)


@dataclass
class Step:
    t0: float
    t1: float
    rows: int          # rows holding a request during the macro-step
    tokens: int        # tokens the step delivered


@dataclass
class Window:
    seconds: float
    served: List[Served]
    steps: List[Step]
    drained: float     # when the drain ended
    trace_span: Optional[tuple] = None  # (start, stop) of the traced slice


def drive(engine, arrivals, seconds: float, *, make_request: Callable,
          drain_s: float = 60.0, trace: Optional[object] = None,
          span: Callable = None, clock: Callable = time.perf_counter,
          sleep: Callable = time.sleep) -> Window:
    """Run the window: ``arrivals`` (sorted by ``due``) are submitted as
    they fall due, the engine is stepped while it holds work, and
    requests still in flight when the window closes are drained for at
    most ``drain_s`` more seconds.

    ``trace``, if given, has ``start_s``, ``stop_s``, ``start()`` and
    ``stop()``: the profiler is started and stopped between steps, so
    the traced slice holds whole steps.  ``span(name)`` gives a context
    manager around each host phase (``wait_arrival``, ``submit``,
    ``step``); by default none.
    """
    span = span or (lambda name: nullcontext())
    served = [Served(i, a.due, a.prompt, a.max_new)
              for i, a in enumerate(arrivals)]
    steps: List[Step] = []
    live: List[Served] = []
    t_base = clock()

    def now():
        return clock() - t_base

    nxt = 0
    tracing, trace_span = False, None
    t = now()
    while True:
        if trace is not None:
            if not tracing and trace_span is None and t >= trace.start_s:
                trace.start()
                tracing, trace_span = True, (now(), None)
            elif tracing and t >= trace.stop_s:
                trace.stop()
                tracing, trace_span = False, (trace_span[0], now())
        if nxt < len(served) and served[nxt].due <= t:
            with span("submit"):
                while nxt < len(served) and served[nxt].due <= t:
                    s = served[nxt]
                    s.req = make_request(s)
                    engine.submit(s.req)
                    s.submit = now()
                    live.append(s)
                    nxt += 1
        busy = bool(engine.queue) or any(r is not None for r in engine.rows)
        if not busy:
            if nxt == len(served):
                break
            with span("wait_arrival"):
                sleep(max(0.0, min(served[nxt].due - now(), 0.05)))
            t = now()
            continue
        if t >= seconds + drain_s:
            break
        t0 = now()
        with span("step"):
            done = engine.step()
        t1 = now()
        rows = sum(r is not None for r in engine.rows) + len(done)
        delivered = 0
        for s in live:
            n = len(s.req.out_tokens)
            if s.row is None and (s.req.t_admit is not None or n):
                s.row = t1
            if n > s.n_out:
                if s.n_out == 0:
                    s.first = t1
                delivered += n - s.n_out
                if t1 <= seconds:
                    s.n_out_window += n - s.n_out
                s.n_out, s.last = n, t1
        live = [s for s in live if not s.finished and s.req.error is None]
        steps.append(Step(t0, t1, rows, delivered))
        t = now()
    if tracing:
        trace.stop()
        trace_span = (trace_span[0], now())
    end = now()
    for s in served:
        if s.req is not None:
            s.out = list(s.req.out_tokens)
    return Window(seconds, served, steps, end, trace_span)
