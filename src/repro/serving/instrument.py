"""Dispatch counting for the serving engines' jitted callables.

The hot-loop contract (SERVING.md §The decode hot loop) is quantitative:
steady-state decode must cost at most ``1/K`` jit dispatches and host
syncs per generated token.  That claim rots silently — a stray
``np.asarray`` or an accidentally un-fused call re-introduces per-token
overhead without failing any parity test.  This module makes it
testable: every engine keeps its jitted programs in a ``_jits`` dict
(name -> callable) and always invokes them through the dict, so
:func:`instrument` can swap in counting wrappers without touching
engine code — including programs compiled *after* instrumentation (the
per-K macro-step jits are built lazily).

    eng = PagedServingEngine(cfg, decode_steps=8)
    counts = instrument(eng)
    ...
    counts.decode_dispatches / eng.tokens_generated   # <= 1/K + prefill

Counter keys are the ``_jits`` names (``decode{k}``, ``verify{s}``,
``prefill``, ``reset``); pipelined engines' per-stage programs are
prefixed ``s{i}.`` and a ModelDraft provider's programs ``draft.``.
tests/test_engine_macro.py pins the dispatches-per-token regression;
benchmarks/engine_bench.py and benchmarks/spec_bench.py report the
same numbers per engine/K cell.
"""
from __future__ import annotations

from collections import Counter


class DispatchCounter(dict):
    """A ``_jits`` dict whose entries are wrapped to count invocations.

    Replaces an engine's (or stage's) ``_jits`` mapping in place-of:
    existing entries are re-wrapped on construction, and entries added
    later (lazily compiled macro-step programs) are wrapped by
    ``__setitem__`` as they appear.  ``counts`` maps jit name ->
    invocation count; one invocation == one jit dispatch (the wrapped
    callables are the engines' compiled programs).
    """

    def __init__(self, base: dict, counts: Counter, prefix: str = "",
                 raw: dict = None):
        super().__init__()
        self.counts = counts
        self.prefix = prefix
        self.raw = {} if raw is None else raw
        for name, fn in base.items():
            self[name] = fn

    def __setitem__(self, name, fn):
        key = self.prefix + name
        self.raw[key] = fn

        def counted(*args, _fn=fn, _key=key, **kw):
            self.counts[_key] += 1
            return _fn(*args, **kw)

        dict.__setitem__(self, name, counted)


class EngineCounts:
    """Per-engine dispatch tallies with the derived hot-loop ratios."""

    def __init__(self, engine):
        self.engine = engine
        self.counts: Counter = Counter()
        self.raw: dict = {}  # jit name -> underlying (unwrapped) callable

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
    any) with dispatch counters.  Counting starts now: tallies cover
    only calls made after instrumentation."""
    ec = EngineCounts(engine)
    engine._jits = DispatchCounter(engine._jits, ec.counts, raw=ec.raw)
    for i, st in enumerate(getattr(engine, "stages", [])):
        st._jits = DispatchCounter(st._jits, ec.counts, prefix=f"s{i}.",
                                   raw=ec.raw)
    spec = getattr(engine, "spec", None)
    if spec is not None and hasattr(spec.provider, "_jits"):
        spec.provider._jits = DispatchCounter(
            spec.provider._jits, ec.counts, prefix="draft.", raw=ec.raw)
    return ec
