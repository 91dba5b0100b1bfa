"""One run of one cell: set-up, warm-up, the open-loop window, the
readings, the correctness check.

``run.py`` calls :func:`run_cell` after it has found the chip; the tests
call the same stages on the CPU at a reduced size.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import shutil
import tempfile
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import numpy as np

from chipbench import bench, check, loop
from chipbench import trace as trace_mod
from chipbench.gen.openloop import rng

#: how long the drain after the window may run before the requests still
#: in flight count as failed
DRAIN_S = 60.0
#: served tokens the correctness sample holds at least
CHECK_TOKENS = 400
#: names of the engine's jitted programs, for the host spans
SPAN_NAMES = ("wait_arrival", "submit", "step", "prefill", "reset", "cow")
#: seconds of the window the profiler records, centred in it
TRACE_SLICE_S = 3.0


# ----------------------------------------------------------------------
# set-up
# ----------------------------------------------------------------------
def model_config(config: dict):
    """The program's ``ModelConfig`` for the configuration file's
    ``model``.  A key the program has no field for must be a switch the
    served model leaves off (it is there for the reference)."""
    from repro.config import ModelConfig
    m = dict(config["model"])
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kind = m.pop("block_kind")
    for k in set(m) - fields:
        v = m.pop(k)
        if v and not k.endswith("_eps"):
            raise ValueError(f"{config['name']}: {k}={v!r} is not served "
                             "by the program")
    m["block_pattern"] = tuple([kind] * m["n_layers"])
    return ModelConfig(**m)


def weight_key(seed: int):
    s = np.random.SeedSequence([int(seed) % 2 ** 64, 1])
    return jax.random.PRNGKey(int(s.generate_state(1)[0]) & 0x7FFFFFFF)


@dataclass
class Setup:
    spec: dict
    engine: object
    weights: dict
    mcfg: object
    recorder: "Recorder"


class Recorder(dict):
    """The engine's ``_jits`` with every call recorded (and, when spans
    are on, wrapped in a profiler annotation of the program's name).
    Entries added later (lazily built decode programs) are wrapped as
    they appear, as ``serving/instrument.py`` does."""

    def __init__(self, base: dict):
        super().__init__()
        self.calls: List[tuple] = []
        self.on = False
        self.tracing = False
        self.spans = False
        for k, v in base.items():
            self[k] = v

    def __setitem__(self, name, fn):
        def recorded(*args, _fn=fn, _name=name):
            if self.on:
                self.calls.append((_name, self.tracing, args[2:4]))
            if self.spans:
                with jax.profiler.TraceAnnotation(_name):
                    return _fn(*args)
            return _fn(*args)

        dict.__setitem__(self, name, recorded)


def build(spec: dict, seed: int) -> Setup:
    """Weights on the device from the seed (one program), then the
    engine as ``launch/serve.py`` builds it, with the cell's rows, length
    and blocks."""
    from repro.launch.serve import ENGINE_SHAPE
    from repro.models import build_model
    from repro.serving.engine import PagedServingEngine
    config = spec["config"]
    ref = bench.reference(config)
    mcfg = model_config(config)
    key = weight_key(seed)
    weights = jax.jit(functools.partial(ref.make_weights,
                                        config["model"]))(key)
    params = ref.to_program(config["model"], weights)
    want = jax.eval_shape(build_model(mcfg).init, key)
    same = jax.tree.structure(want) == jax.tree.structure(params) and all(
        a.shape == b.shape and a.dtype == b.dtype
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(params)))
    if not same:
        raise ValueError(f"{config['name']}: the benchmark's weights do not "
                         "match the program's parameter tree")
    shape = dict(ENGINE_SHAPE, **config["engine"])
    engine = PagedServingEngine(mcfg, params, **shape)
    recorder = Recorder(engine._jits)
    engine._jits = recorder
    return Setup(spec, engine, weights, mcfg, recorder)


def reseed(setup: Setup, seed: int):
    """New weights from ``seed`` in the same engine, so the same compiled
    programs serve them (the correctness readings over many seeds)."""
    config = setup.spec["config"]
    ref = bench.reference(config)
    setup.engine.params = None
    setup.weights = None
    gc.collect()
    setup.weights = jax.jit(functools.partial(
        ref.make_weights, config["model"]))(weight_key(seed))
    setup.engine.params = ref.to_program(config["model"], setup.weights)


def warm(setup: Setup, seed: int):
    """Compile and run every program shape the cell's traffic can use,
    and no other: one prompt whose prefill (all but its last token) is a
    full chunk plus every power-of-two tail below it, then one request
    per decode scan length (powers of two up to K), each alone."""
    from repro.serving.engine import Request
    eng = setup.engine
    vocab = setup.mcfg.vocab_size
    g = rng(seed, 7)
    chunk, k = eng.prefill_chunk, eng.decode_k
    plan = [(2 * chunk, 1)]
    while k >= 2:
        plan.append((16, k))
        k //= 2
    for i, (n_prompt, n_new) in enumerate(plan):
        prompt = g.integers(1, vocab, size=n_prompt).tolist()
        eng.submit(Request(id=-1 - i, prompt=prompt, max_new_tokens=n_new))
        done = eng.run()
        if len(done) != 1 or eng.rejected:
            raise RuntimeError(f"warm-up request {i} did not finish")


# ----------------------------------------------------------------------
# the window
# ----------------------------------------------------------------------
class Tracer:
    """Profiler start/stop for a steady slice of the window."""

    def __init__(self, seconds: float, recorder: Recorder):
        slice_s = min(TRACE_SLICE_S, seconds / 4)
        self.start_s = max(0.0, seconds / 2 - slice_s / 2)
        self.stop_s = self.start_s + slice_s
        self.recorder = recorder
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")

    def start(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.recorder.tracing = True

    def stop(self):
        self.recorder.tracing = False
        jax.profiler.stop_trace()

    def done(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def count_compiles():
    """A live count of XLA compilations (backend compiles) in this
    process."""
    box = {"n": 0}

    def on(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            box["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on)
    return box


def gc_pauses():
    """A live sum of the seconds Python's collector held this process,
    with the longest single pause (to tell host stalls apart)."""
    box = {"s": 0.0, "longest_s": 0.0, "t0": 0.0}

    def on(phase, info):
        if phase == "start":
            box["t0"] = time.perf_counter()
        else:
            d = time.perf_counter() - box["t0"]
            box["s"] += d
            box["longest_s"] = max(box["longest_s"], d)

    gc.callbacks.append(on)
    return box, on


@dataclass
class Run:
    """What one window produced, for the metric readers."""
    spec: dict
    window: loop.Window
    calls: List[tuple]            # (name, traced, info) per program call
    counters: Dict[str, float]
    memory: Dict[str, int]
    max_rows: int
    setup_s: float
    trace: Optional[trace_mod.Summary] = None
    peaks: Optional[dict] = None
    compiles: int = 0
    collector: Optional[dict] = None   # gc pause seconds in the window


def _calls(recorder: Recorder):
    """Program calls with their sizes read back to the host (after the
    window, so the reads cost the window nothing)."""
    out = []
    for name, traced, args in recorder.calls:
        if name.startswith("decode"):
            batch = args[0]
            info = {"pos": np.asarray(batch["pos"]),
                    "budget": np.asarray(batch["budget"]),
                    "k": int(name[len("decode"):])}
        elif name == "prefill":
            info = {"c": int(args[0].shape[1]), "p0": int(args[1])}
        else:
            info = {}
        out.append((name, traced, info))
    return out


def _counters(eng) -> Dict[str, float]:
    return {"tokens_generated": eng.tokens_generated,
            "prefill_tokens": eng.prefill_tokens,
            "prefix_tokens_hit": eng.pc.prefix_tokens_hit}


def measure(setup: Setup, seed: int, seconds: float, *, trace: bool,
            t_start: float, device=None) -> Run:
    """Drive the window at the cell's fixed rate and read what it did."""
    from repro.serving.engine import Request
    spec, eng = setup.spec, setup.engine
    gen = bench.generator(spec["traffic"])
    arrivals = gen.schedule(spec["traffic"], spec["cell"]["rate_rps"],
                            seconds, seed, setup.mcfg.vocab_size)
    tracer = Tracer(seconds, setup.recorder) if trace else None
    setup.recorder.spans = trace

    def span(name):
        return jax.profiler.TraceAnnotation(name)

    before = _counters(eng)
    compiles = count_compiles()
    pauses, listener = gc_pauses()
    setup.recorder.on = True
    setup_s = time.perf_counter() - t_start
    window = loop.drive(
        eng, arrivals, seconds,
        make_request=lambda s: Request(id=s.id, prompt=list(s.prompt),
                                       max_new_tokens=s.max_new),
        drain_s=DRAIN_S, trace=tracer, span=span if trace else None)
    setup.recorder.on = False
    gc.callbacks.remove(listener)
    n_compiles = compiles["n"]
    after = _counters(eng)
    memory = {}
    if device is not None:
        stats = device.memory_stats() or {}
        memory = {k: int(stats.get(k, 0)) for k in
                  ("peak_bytes_in_use", "peak_bytes_reserved")}
    run = Run(spec, window, _calls(setup.recorder),
              {k: after[k] - before[k] for k in after}, memory,
              eng.max_rows, setup_s, compiles=n_compiles,
              collector={k: pauses[k] for k in ("s", "longest_s")})
    if tracer is not None:
        run.trace = trace_mod.summarize(tracer.dir, SPAN_NAMES)
        tracer.done()
    return run


def free_program(setup: Setup):
    """Drop the engine and its caches before the reference runs (a
    process's peak never falls again, so the reference runs last)."""
    setup.engine.caches = None
    setup.engine = None
    setup.recorder = None
    gc.collect()


def correctness(setup: Setup, run: Run, seed: int,
                control: bool = False) -> dict:
    """Compare a seeded sample of the window's finished requests with the
    reference; ``correct`` also needs every due request finished.  With
    ``control`` the float8 reference stands in the program's place and is
    held to the same limit (it has to come out not correct)."""
    config = setup.spec["config"]
    served = run.window.served
    picked = check.sample(served, seed, CHECK_TOKENS)
    got = check.compare(config, setup.weights, picked, control=control)
    limit = config["check"]["logit_gap_max"]
    unserved = sum(not s.finished for s in served)
    got["checks"] = {
        "logit_gap_max": {"value": got["logit_gap_max"], "limit": limit},
        "unserved_requests": {"value": unserved, "limit": 0},
    }
    got["correct"] = bool(picked) and all(
        c["value"] <= c["limit"] for c in got["checks"].values())
    return got


# ----------------------------------------------------------------------
# metrics
# ----------------------------------------------------------------------
def read_metrics(run: Run, names: List[dict]) -> Dict[str, dict]:
    """Each metric from its reader; a reader that finds nothing to read
    returns None and the metric is left out."""
    out = {}
    for m in names:
        value = bench.metric_reader(m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def breakdown(summary: trace_mod.Summary) -> dict:
    return {"device_ops": [[n, s] for n, s in summary.top_ops[:10]],
            "idle_gaps": [[n, s] for n, s in summary.idle_by_span[:10]]}


def device_info(device, count: int, memory: dict) -> dict:
    return {"platform": device.platform, "kind": device.device_kind,
            "count": count,
            "memory_peak_bytes": int(memory.get("peak_bytes_in_use", 0)
                                     + memory.get("peak_bytes_reserved", 0))}


def host_stalls(run: Run) -> dict:
    """The longest host-clock pauses of the window: the longest engine
    step, the longest gap between two steps (waits for an arrival
    included), and the collector's pauses.  Not metrics: they say where
    a slow run lost its time."""
    steps = run.window.steps
    gaps = [b.t0 - a.t1 for a, b in zip(steps, steps[1:])]
    return {"longest_step_s": max((s.t1 - s.t0 for s in steps), default=0.0),
            "longest_gap_s": max(gaps, default=0.0),
            "gc_s": run.collector["s"],
            "gc_longest_s": run.collector["longest_s"]}


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             t_start: float, device, count: int) -> dict:
    """The whole run; returns the result line's object."""
    setup = build(spec, seed)
    warm(setup, seed)
    jax.block_until_ready(setup.weights)
    run = measure(setup, seed, seconds, trace=trace, t_start=t_start,
                  device=device)
    if trace:
        run.peaks = bench.peaks(device.device_kind)
    metrics = read_metrics(run, spec["per_layer"] if trace
                           else spec["end_to_end"])
    free_program(setup)
    t_check = time.perf_counter()
    got = correctness(setup, run, seed)
    got["check_s"] = time.perf_counter() - t_check
    window = run.window
    out = {"correct": got["correct"],
           "attempted": len(window.served),
           "failed": sum(not s.finished for s in window.served),
           "metrics": metrics,
           "device": device_info(device, count, run.memory)}
    if trace:
        out["device"]["busy_s"] = run.trace.busy_s
        out["device"]["window_s"] = run.trace.window_s
        out["breakdown"] = breakdown(run.trace)
    out["compiles_in_window"] = run.compiles
    out["host_stalls"] = host_stalls(run)
    out["check_detail"] = {k: got[k] for k in
                           ("tokens", "requests", "top1_agree", "check_s")}
    out["checks"] = got["checks"]
    return out
