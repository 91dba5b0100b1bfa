"""Chip smoke run: the served path once, end to end, at the full published
width of SmolLM-360M on one TPU chip.

  python chip_smoke.py [--seed N]

One process, in this order:

1. device: refuse to run unless JAX's platform is ``tpu``;
2. JAX's persistent compilation cache (``launch/serve.py``);
3. full-width ``smollm-360m`` in bf16, params made from ``--seed``;
4. the paper's loop (``launch/serve.py``'s ``paper_loop``, which
   ``examples/serve_batched.py`` also runs): decompose into two core
   stages, profile the jitted forward at 4 x 512 on the chip, then the
   static IP and Algorithm 1 through ``Simulator``;
5. 16 seeded requests through ``PagedServingEngine``, then through
   ``PagedPipelinedEngine`` with two stages placed by the static IP.
   Each engine serves the requests once to compile (warm-up) and once
   more in the serve window;
6. fatal checks: every request finishes; each first generated token is
   the argmax of ``Model.forward`` over its prompt (see ``GAP_TOL``);
   nothing compiles in either serve window.

Any failure exits non-zero before the result line.  Earlier lines are
smoke readings, not benchmark numbers.  The last line of standard
output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.configs import get_config  # noqa: E402
from repro.launch.serve import (ENGINE_SHAPE, paper_loop,  # noqa: E402
                                serve, setup_compile_cache, synth_prompts)
from repro.models import build_model  # noqa: E402
from repro.serving.engine import PagedServingEngine  # noqa: E402
from repro.serving.instrument import instrument  # noqa: E402
from repro.serving.pipeline import (PagedPipelinedEngine,  # noqa: E402
                                    place_stages)

#: Exemption from the first-token check, in units of the standard
#: deviation of the prompt's last-position logits.  The engine reaches
#: that token through chunked prefill into the paged cache and one
#: decode step, the reference through one causal forward: the same bf16
#: math in another order, whose rounding (2^-8 relative per op, over 32
#: layers) should move logits by well under 5% of their spread.  Where the
#: reference's top two logits lie closer than that, either token is a
#: correct answer.
GAP_TOL = 0.05

N_REQUESTS = 16
MAX_NEW = 64
MAX_ROWS = 8
MAX_LEN = 2048


def fail(msg: str):
    sys.exit(f"chip_smoke: FAILED: {msg}")


def log(msg: str):
    print(msg, flush=True)


def count_compile_cache_events() -> Counter:
    """Live counts of JAX's persistent compilation cache events, keyed by
    the event's last path part: ``compile_requests_use_cache`` (compiles
    that consulted the cache), ``cache_hits`` (served from it) and
    ``cache_misses`` (compiled and written to it; JAX writes only
    programs that took at least
    ``jax_persistent_cache_min_compile_time_secs`` to compile)."""
    counts: Counter = Counter()

    def on_event(event: str, **_):
        if event.startswith("/jax/compilation_cache/"):
            counts[event.rsplit("/", 1)[1]] += 1

    jax.monitoring.register_event_listener(on_event)
    return counts


def reference_first_tokens(model, params, prompts, batch: int):
    """Argmax, top-two gap and spread of ``Model.forward``'s logits at
    each prompt's last position.  Prompts are right-padded to one length
    (causal attention keeps padding out of earlier positions), so one
    program serves every batch."""
    vocab = model.cfg.vocab_size
    width = max(len(p) for p in prompts)

    @jax.jit
    def last_logits(p, toks, last):
        logits = model.forward(p, {"tokens": toks})[0]
        row = jnp.take_along_axis(logits, last[:, None, None], axis=1)
        row = row[:, 0, :vocab].astype(jnp.float32)
        top2 = jax.lax.top_k(row, 2)[0]
        return (jnp.argmax(row, axis=-1), top2[:, 0] - top2[:, 1],
                jnp.std(row, axis=-1))

    outs = []
    for i in range(0, len(prompts), batch):
        chunk = prompts[i:i + batch]
        chunk = chunk + [chunk[-1]] * (batch - len(chunk))
        toks = np.zeros((batch, width), np.int32)
        for r, p in enumerate(chunk):
            toks[r, :len(p)] = p
        last = np.asarray([len(p) - 1 for p in chunk], np.int32)
        outs.append([np.asarray(a) for a in last_logits(params, toks, last)])
    top, gap, spread = (np.concatenate(c)[:len(prompts)] for c in zip(*outs))
    return top, gap, spread


def check_first_tokens(name: str, done, ref):
    top, gap, spread = ref
    exempt, mismatched = [], []
    for r in done:
        if r.out_tokens[0] == int(top[r.id]):
            continue
        if gap[r.id] < GAP_TOL * spread[r.id]:
            exempt.append(r.id)
        else:
            mismatched.append((r.id, r.out_tokens[0], int(top[r.id]),
                               float(gap[r.id] / spread[r.id])))
    log(f"{name}: first token = forward argmax for "
        f"{len(done) - len(exempt) - len(mismatched)}/{len(done)}; "
        f"exempt (top-two gap < {GAP_TOL} x spread): {exempt}")
    if mismatched:
        fail(f"{name}: first token differs from Model.forward's argmax "
             f"(id, engine, reference, gap/spread): {mismatched}")


def serve_window(name: str, engine, prompts, max_new: int):
    """Warm-up serve (compiles every program the requests need), then
    the serve window over the same requests; fails on any compile in
    the window."""
    counts = instrument(engine)
    warm, warm_s = serve(engine, prompts, max_new)
    n_programs = counts.compiled_programs()
    done, wall = serve(engine, prompts, max_new)
    compiled = counts.compiled_programs() - n_programs
    toks = sum(len(r.out_tokens) for r in done)
    same = all(a.out_tokens == b.out_tokens for a, b in zip(warm, done))
    log(f"{name}: warm-up {warm_s} s ({n_programs} programs compiled); "
        f"window {len(done)} requests, {toks} tokens in {wall} s; "
        f"window streams equal warm-up streams: {same}")
    if compiled:
        fail(f"{name}: {compiled} programs compiled inside the serve window")
    return done


def run(cfg, *, seed: int, n_requests: int, max_new: int, max_rows: int,
        max_len: int, profile_batch=(4, 512)):
    """Phases 3-6 on whatever device JAX has; ``main`` pins it to a TPU."""
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    n_params = sum(int(a.size) for a in jax.tree.leaves(params))
    log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab_size}, {n_params} params, dtype {cfg.dtype}")

    loop = paper_loop(cfg, model, params, seed=seed,
                      profile_batch=profile_batch, horizon_slots=20,
                      drain_slots=100)
    log(f"profiled forward {profile_batch[0]}x{profile_batch[1]}: stage ms "
        f"{loop.stage_ms}")
    m = loop.metrics
    if not all(np.isfinite(m[k]) for k in ("on_time", "completed",
                                          "total_cost")):
        fail(f"simulator metrics not finite: {m}")
    log(f"edge sim: on_time={m['on_time']} completed={m['completed']} "
        f"cost={m['total_cost']}")
    placement = place_stages(loop.app, loop.net, "static_ip")
    log(f"static-IP stage placement: {placement}")
    prompts = synth_prompts(cfg.vocab_size, n_requests, max_len, seed)
    log(f"prompt lengths: {[len(p) for p in prompts]}")
    ref = reference_first_tokens(model, params, prompts,
                                 batch=profile_batch[0])

    eng = PagedServingEngine(cfg, params, max_rows=max_rows,
                             max_len=max_len, **ENGINE_SHAPE)
    mono = serve_window("paged", eng, prompts, max_new)
    log(f"paged: prefix tokens served from shared blocks "
        f"{eng.pc.prefix_tokens_hit}")
    check_first_tokens("paged", mono, ref)
    del eng

    eng = PagedPipelinedEngine(cfg, params, n_stages=2, max_rows=max_rows,
                               max_len=max_len, net=loop.net,
                               placement=placement, **ENGINE_SHAPE)
    pipe = serve_window("pipelined", eng, prompts, max_new)
    check_first_tokens("pipelined", pipe, ref)
    del eng

    agree = sum(x == y for a, b in zip(mono, pipe)
                for x, y in zip(a.out_tokens, b.out_tokens))
    total = sum(len(a.out_tokens) for a in mono)
    log(f"paged vs pipelined: {agree}/{total} tokens agree "
        f"({agree / total})")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX found platform {dev.platform!r}")
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    log(f"compile cache: {setup_compile_cache()}")
    cache_events = count_compile_cache_events()
    run(get_config("smollm-360m"), seed=args.seed, n_requests=N_REQUESTS,
        max_new=MAX_NEW, max_rows=MAX_ROWS, max_len=MAX_LEN)
    stats = dev.memory_stats() or {}
    for key in ("peak_bytes_in_use", "peak_bytes_reserved"):
        log(f"{key}: {stats.get(key, 'not reported')}")
    log(f"persistent compile cache events: {dict(cache_events)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
