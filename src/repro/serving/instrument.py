"""Dispatch counts, spans, request marks and compile counts for the
serving engines.

**Dispatch counting.**  The hot-loop contract (SERVING.md §The decode
hot loop) is quantitative: steady-state decode must cost at most ``1/K``
jit dispatches and host syncs per generated token.  That claim rots
silently — a stray ``np.asarray`` or an accidentally un-fused call
re-introduces per-token overhead without failing any parity test.  This
module makes it testable: every engine keeps its jitted programs in a
``_jits`` dict (name -> callable) and always invokes them through the
dict, so :func:`instrument` can swap in counting wrappers without
touching engine code — including programs compiled *after*
instrumentation (the per-K macro-step jits are built lazily).

    eng = PagedServingEngine(cfg, decode_steps=8)
    counts = instrument(eng)
    ...
    counts.decode_dispatches / eng.tokens_generated   # <= 1/K + prefill

Counter keys are the ``_jits`` names (``decode{k}``, ``verify{s}``,
``prefill``, ``reset``, ``cow``); pipelined engines' per-stage programs
are prefixed ``s{i}.`` and a ModelDraft provider's programs ``draft.``.
A paged engine's ``decode{k}`` calls also add up the KV blocks its
decode attention reads against a whole-table gather
(:meth:`EngineCounts.count_kv_blocks`).
tests/test_engine_macro.py pins the dispatches-per-token regression;
benchmarks/engine_bench.py and benchmarks/spec_bench.py report the
same numbers per engine/K cell.

**The engine trace.**  :class:`EngineTrace` records where an engine
spends its host time and when each request passes each point of its
life, on a clock its caller gives:

    counts = instrument(eng)
    trace = EngineTrace(time.perf_counter).attach(eng)
    ...
    trace.spans, trace.marks, trace.compiles
    trace.close()

- *spans* (:data:`SPAN_NAMES`): the engine's phases, nested under one
  ``engine.step`` root per :meth:`step`; each program call through
  ``_jits`` runs inside a span of its ``_jits`` name.  Every span is
  also a ``jax.profiler.TraceAnnotation``, so a profiler trace holds
  the same spans on its own clock (an annotation costs next to nothing
  while no profiler runs).
- *marks* (:data:`MARKS`): ``submit``, ``admit``, ``prefilled`` (the
  last prefill chunk *dispatched*: the device runs it later, before
  the next macro-step) and ``first`` (the first token on the host),
  keyed by request id.
- *compiles*: XLA backend compiles, by the innermost span open when
  each happened (the program's own span for a program that compiles
  on its first call).

The trace counts nothing the engine already counts
(``tokens_generated``, ``prefill_tokens``, ``pc.prefix_tokens_hit``,
``n_preemptions``, ``pc.n_meta_uploads``): read those on the engine.

An engine holds :data:`NULL_TRACE` until one is attached: its ``span``
returns one shared ``nullcontext`` and its ``mark`` returns at once, so
an untraced engine pays one attribute lookup and one call per site.
"""
from __future__ import annotations

from collections import Counter, defaultdict
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

#: the engine's own spans (program spans carry their ``_jits`` names)
SPAN_NAMES = ("engine.step", "engine.admit", "engine.prefill_prep",
              "engine.grow", "engine.cow", "engine.decode_prep",
              "engine.sync", "engine.deliver", "engine.release")
#: the points of a request's life, in the order it lives them
MARKS = ("submit", "admit", "prefilled", "first")
#: the ``jax.monitoring`` event of one XLA backend compile
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclass
class Span:
    """One span: ``parent`` is the enclosing span's id (-1 for a
    root)."""
    id: int
    parent: int
    name: str
    t0: float
    t1: Optional[float] = None      # None while open

    @property
    def duration(self) -> float:
        return self.t1 - self.t0


@dataclass(frozen=True)
class Mark:
    request: int
    event: str
    t: float


class NullTrace:
    """The trace of an engine that nobody traces: records nothing."""

    _NONE = nullcontext()

    def span(self, name: str):
        return self._NONE

    def mark(self, request: int, event: str):
        return None


NULL_TRACE = NullTrace()


class EngineTrace:
    """Spans, request marks and compile counts of one engine, in memory.

    ``clock`` is the caller's: a callable returning seconds.  Nothing
    under ``serving/`` reads a wall clock (reprolint's ``step-clock``
    rule); a benchmark passes the clock its own loop stamps with, so
    marks and the loop's stamps compare directly.  The clock only
    *records*: no scheduling decision reads it.  The engine decides by
    its step counter ``t`` alone, so token streams and ``Request.t_*``
    stamps are the same with a trace attached as without
    (tests/test_instrument.py).

    :meth:`attach` gives the trace to an engine; program spans come
    from the ``_jits`` wrappers of :func:`instrument`, which wraps an
    engine once, while a trace is attached per measured window.  The
    compile listener stays registered with ``jax.monitoring`` until
    :meth:`close`, which also detaches the trace from its engine.
    """

    def __init__(self, clock: Callable[[], float]):
        self.clock = clock
        self.spans: List[Span] = []
        self.marks: List[Mark] = []
        self.compiles: Counter = Counter()
        self.engine = None
        self._open: List[Span] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    # -------------------------------------------------------------- record
    def attach(self, engine) -> "EngineTrace":
        """Make this the trace of ``engine``; returns the trace."""
        self.engine = engine
        engine.trace = self
        return self

    def close(self):
        """Detach from the engine and stop counting compiles."""
        if self.engine is not None and self.engine.trace is self:
            self.engine.trace = NULL_TRACE
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    @contextmanager
    def span(self, name: str):
        parent = self._open[-1].id if self._open else -1
        s = Span(len(self.spans), parent, name, self.clock())
        self.spans.append(s)
        self._open.append(s)
        try:
            with jax.profiler.TraceAnnotation(name):
                yield s
        finally:
            self._open.pop()
            s.t1 = self.clock()

    def mark(self, request: int, event: str):
        self.marks.append(Mark(request, event, self.clock()))

    def _on_event(self, event: str, duration: float, **_):
        if event == COMPILE_EVENT and self._open:
            self.compiles[self._open[-1].name] += 1

    # ---------------------------------------------------------------- read
    def children(self) -> Dict[int, List[Span]]:
        """Closed spans by their parent's id."""
        out: Dict[int, List[Span]] = defaultdict(list)
        for s in self.spans:
            if s.t1 is not None and s.parent >= 0:
                out[s.parent].append(s)
        return out

    def roots(self, name: str = "engine.step") -> List[Span]:
        """Closed top-level spans of ``name``, in order."""
        return [s for s in self.spans
                if s.parent < 0 and s.name == name and s.t1 is not None]

    def phases(self, root: Span,
               kids: Optional[Dict[int, List[Span]]] = None
               ) -> Dict[str, float]:
        """Self time by span name over ``root`` and every span under it:
        a span's duration less what its children cover (they run one
        after another on the engine's thread, so they never overlap)."""
        kids = self.children() if kids is None else kids
        out: Dict[str, float] = defaultdict(float)
        todo = [root]
        while todo:
            s = todo.pop()
            below = kids.get(s.id, [])
            out[s.name] += s.duration - sum(c.duration for c in below)
            todo += below
        return dict(out)

    def first_marks(self) -> Dict[int, Dict[str, float]]:
        """Each request's marks: the time of the first of each event."""
        out: Dict[int, Dict[str, float]] = defaultdict(dict)
        for m in self.marks:
            out[m.request].setdefault(m.event, m.t)
        return dict(out)

    def request_times(self, until: float) -> Dict[str, List[float]]:
        """Per request, the parts of its wait, each taken where its later
        mark falls by ``until`` (on the trace's clock):

        - ``admit_wait``: ``admit - submit``, the wait for a row;
        - ``prefill_dispatch``: ``prefilled - admit``, the host's
          admission and dispatch of every prefill chunk.  The chunks
          run on the device after that, before the next macro-step, so
          their device time falls in ``admit_to_first``;
        - ``admit_to_first``: ``first - admit``: the prefill and the
          whole first macro-step, to the token's arrival on the host.
        """
        parts = {"admit_wait": ("submit", "admit"),
                 "prefill_dispatch": ("admit", "prefilled"),
                 "admit_to_first": ("admit", "first")}
        out: Dict[str, List[float]] = {k: [] for k in parts}
        for m in self.first_marks().values():
            for key, (a, b) in parts.items():
                if a in m and b in m and m[b] <= until:
                    out[key].append(m[b] - m[a])
        return out

    def host_step_times(self, until: float) -> List[float]:
        """Each ``engine.step`` that ended by ``until``, less its
        ``engine.sync``: the step's host work while it does not wait on
        the chip."""
        kids = self.children()
        return [s.duration - self.phases(s, kids).get("engine.sync", 0.0)
                for s in self.roots() if s.t1 <= until]

    def longest_step(self) -> dict:
        """The longest ``engine.step`` (``s``) and its self time by span
        (``phases``); ``{}`` before the first step."""
        roots = self.roots()
        if not roots:
            return {}
        s = max(roots, key=lambda r: r.duration)
        return {"s": s.duration, "phases": self.phases(s)}


class DispatchCounter(dict):
    """A ``_jits`` dict whose entries are wrapped to count invocations,
    to log them, and to run each inside a span of the owning engine's
    trace.

    Replaces an engine's (or stage's) ``_jits`` mapping in place:
    existing entries are re-wrapped on construction, and entries added
    later (lazily compiled macro-step programs) are wrapped by
    ``__setitem__`` as they appear.  ``ec.counts`` maps jit name ->
    invocation count; one invocation == one jit dispatch (the wrapped
    callables are the engines' compiled programs).  The trace is read
    from the owning engine at each call, so attaching or closing one
    needs no re-wrapping.
    """

    def __init__(self, base: dict, ec: "EngineCounts", owner,
                 prefix: str = ""):
        super().__init__()
        self.ec = ec
        self.owner = owner
        self.prefix = prefix
        for name, fn in base.items():
            self[name] = fn

    def __setitem__(self, name, fn):
        key = self.prefix + name
        self.ec.raw[key] = fn
        ec = self.ec

        decode_k = (int(name[len("decode"):])
                    if not self.prefix and name.startswith("decode")
                    and name[len("decode"):].isdigit() else 0)

        def counted(*args, _fn=fn, _key=key, **kw):
            ec.counts[_key] += 1
            if decode_k:
                ec.count_kv_blocks(decode_k)
            if ec.calls is not None:
                ec.calls.append((_key, ec.tracing, args[2:4]))
            with self.owner.trace.span(_key):
                return _fn(*args, **kw)

        dict.__setitem__(self, name, counted)


class EngineCounts:
    """Per-engine dispatch tallies with the derived hot-loop ratios, and
    a log of the program calls while ``calls`` is a list.

    The log holds ``(name, traced, args[2:4])`` per call: the inputs
    past the parameters and caches (a decode call's batch, a prefill
    chunk's tokens and start), from which a benchmark sizes each call's
    work, and whether a profiler was on (``tracing``, which the
    profiler's owner sets).  It keeps those arrays alive, so it is off
    (``None``) until a caller sets a list for the calls it wants.
    """

    def __init__(self, engine):
        self.engine = engine
        self.counts: Counter = Counter()
        self.raw: dict = {}  # jit name -> underlying (unwrapped) callable
        self.calls: Optional[list] = None
        self.tracing = False
        self.kv_blocks_live = 0
        self.kv_blocks_table = 0

    def count_kv_blocks(self, k: int):
        """Before a paged engine's ``decode{k}`` call: add the attn-table
        KV blocks one layer's decode attention reads over the ``k``
        iterations.  ``kv_blocks_live``: each row that holds a request,
        ``ceil(min(pos + 1 + i, max_len) / block_size)`` at iteration
        ``i``, what the TPU decode kernel copies (a row that finishes
        inside the scan is counted as if it went on; a free row reads
        nothing).  ``kv_blocks_table``: every row's whole table, what a
        gather of the logical view reads.  Their ratio is the share of
        the gather's KV traffic the kernel keeps."""
        pc = getattr(self.engine, "pc", None)
        if pc is None:
            return
        rows = [i for i, r in enumerate(self.engine.rows) if r is not None]
        slots = np.minimum(self.engine.pos[rows, None] + 1 + np.arange(k),
                           pc.max_len)
        self.kv_blocks_live += int((-(-slots // pc.block_size)).sum())
        self.kv_blocks_table += k * pc.max_rows * pc.nb_logical

    @property
    def decode_dispatches(self) -> int:
        return sum(n for name, n in self.counts.items()
                   if name.rsplit(".", 1)[-1].startswith("decode"))

    @property
    def prefill_dispatches(self) -> int:
        return sum(n for name, n in self.counts.items()
                   if name.rsplit(".", 1)[-1] == "prefill")

    @property
    def verify_dispatches(self) -> int:
        """Fused draft-verify rounds (``verify{K+1}`` programs) —
        deliberately NOT counted as decode dispatches: the hot-loop
        ratio tests pin ``decode_dispatches`` to the plain macro-step
        scan, and a speculative engine's analogue is
        ``verify_dispatches / tokens_generated`` (between 1 and
        1/(K+1))."""
        return sum(n for name, n in self.counts.items()
                   if name.rsplit(".", 1)[-1].startswith("verify"))

    @property
    def draft_dispatches(self) -> int:
        """Draft-provider jit dispatches (``draft.*`` — a ModelDraft's
        prefill chunks and proposal scans; 0 for host-only drafts)."""
        return sum(n for name, n in self.counts.items()
                   if name.startswith("draft."))

    @property
    def total_dispatches(self) -> int:
        return sum(self.counts.values())

    def per_token(self, kind: str = "decode") -> float:
        """Dispatches per generated token (``decode``/``prefill``/
        ``total``)."""
        n = getattr(self, f"{kind}_dispatches")
        return n / max(self.engine.tokens_generated, 1)

    def compiled_programs(self) -> int:
        """Total programs XLA has compiled for the engine's jits: the
        sum of jax's per-callable compilation-cache sizes over every
        (unwrapped) ``_jits`` entry.  Dispatch counts say how often the
        hot loop *calls* its programs; this says how many distinct
        programs those calls traced — the number that silently explodes
        when a shape or a captured Python value stops being stable.
        Caveat: jax shares executable caches by underlying-function
        identity, so jits over module-level functions (``reset``) can
        see other engines' compiles — absolute assertions need a cold
        cache (``jax.clear_caches()``), as test_engine_macro.py does.
        Entries without a compilation cache (e.g. a FakeEngine's plain
        callables) contribute zero, so a result of 0 means 'nothing
        measurable', not 'no compiles'."""
        return sum(fn._cache_size() for fn in self.raw.values()
                   if hasattr(fn, "_cache_size"))


def instrument(engine) -> EngineCounts:
    """Wrap ``engine``'s jitted callables (and its pipeline stages', if
    any) with dispatch counters, which also put each call in a span of
    the engine's trace.  Counting starts now: tallies cover only calls
    made after instrumentation."""
    ec = EngineCounts(engine)
    engine._jits = DispatchCounter(engine._jits, ec, engine)
    for i, st in enumerate(getattr(engine, "stages", [])):
        st._jits = DispatchCounter(st._jits, ec, engine, prefix=f"s{i}.")
    spec = getattr(engine, "spec", None)
    if spec is not None and hasattr(spec.provider, "_jits"):
        spec.provider._jits = DispatchCounter(
            spec.provider._jits, ec, engine, prefix="draft.")
    return ec
