"""Serving entry point: seeded synthetic requests through the paged engine.

  # full published width (on the chip: see chip_smoke.py)
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m
  # reduced config, on the CPU
  PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.serve --smoke \
      --max-len 128 --max-new 8

JAX's persistent compilation cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, else at ``<repo>/.jax_cache`` (:func:`setup_compile_cache`).
"""
from __future__ import annotations

import argparse
import os
import time
from pathlib import Path
from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core.graph import Application
from repro.core.network import EdgeNetwork, make_network
from repro.core.online_controller import ProposalStrategy
from repro.core.simulator import Simulator
from repro.microservice.partition import (decompose, profile_stage_ms,
                                          to_application)
from repro.serving.engine import PagedServingEngine, Request

COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

#: engine shape of the served path: 16-token blocks, K=16 fused decode
#: steps per macro-step, 256-token prefill chunks.  Decode scratch grows
#: with K x rows x max_len (ROADMAP A3), so 8 rows x 2048 is the largest
#: K=16 batch that leaves a v5e chip's 16 GB room for a second engine.
ENGINE_SHAPE = {"block_size": 16, "decode_steps": 16, "prefill_chunk": 256}


def setup_compile_cache() -> str:
    """Give JAX one persistent compilation cache and return its path.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
    itself).  Otherwise the cache is the fixed ``<repo>/.jax_cache``: the
    path is part of the cache key, so it never depends on a temp name,
    pid or time.  Entry points call this before their first compile;
    importing this module configures nothing.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


class PaperLoop(NamedTuple):
    app: Application
    net: EdgeNetwork
    sim: Simulator
    metrics: Dict[str, float]
    stage_ms: Dict[str, float]


def paper_loop(cfg, model, params, *, seed: int,
               profile_batch: Tuple[int, int], horizon_slots: int,
               drain_slots: int) -> PaperLoop:
    """The paper's loop ahead of serving, on whatever device JAX has.

    Decompose ``cfg`` into two core stages, time the jitted forward at
    ``profile_batch`` (rows, tokens) on the device and give each core
    stage half of it, then run the static IP and Algorithm 1
    (``ProposalStrategy``) through ``Simulator`` for ``horizon_slots``
    on an edge network drawn from ``seed``.
    """
    stages = decompose(cfg, n_core_stages=2)
    fwd = jax.jit(lambda p, t: model.forward(p, {"tokens": t})[0])
    full_ms = profile_stage_ms(fwd, params,
                               jnp.ones(profile_batch, jnp.int32))
    stage_ms = {"tokenize": 0.05, "sample": 0.10, "detokenize": 0.05,
                "stage0": full_ms / 2, "stage1": full_ms / 2}
    rng = np.random.default_rng(seed)
    app = to_application(cfg, stages, rng, measured_ms=stage_ms,
                         deadline_ms=80.0, rate=0.3)
    net = make_network(rng)
    sim = Simulator(app, net, ProposalStrategy(kappa=4),
                    rng=np.random.default_rng(seed + 1),
                    horizon_slots=horizon_slots, drain_slots=drain_slots)
    return PaperLoop(app, net, sim, sim.run(), stage_ms)


def synth_prompts(vocab: int, n: int, max_len: int,
                  seed: int) -> List[List[int]]:
    """``n`` seeded prompts sized to a ``max_len`` cache.

    Lengths are drawn uniformly from [max_len/32, max_len/2] (64-1024 at
    2048), so prompt + generation always fits.  Even-numbered prompts
    open with one shared max_len/8-token prefix (256 at 2048), which the
    paged cache serves from shared blocks; they are at least one token
    longer than that prefix.
    """
    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, vocab, size=max_len // 8).tolist()
    lens = rng.integers(max_len // 32, max_len // 2 + 1, size=n)
    prompts = []
    for i, n_tok in enumerate(lens):
        if i % 2 == 0:
            tail = max(int(n_tok) - len(prefix), 1)
            prompts.append(prefix + rng.integers(1, vocab, size=tail).tolist())
        else:
            prompts.append(rng.integers(1, vocab, size=int(n_tok)).tolist())
    return prompts


def serve(engine, prompts: List[List[int]],
          max_new: int) -> Tuple[List[Request], float]:
    """Submit one request per prompt, run ``engine`` until drained, and
    return the finished requests in id order with the wall seconds the
    run took (host clock, ending on the last token copied to the host).

    Raises ``RuntimeError`` unless every request finished: a rejected or
    unfinished request is a failed serve, not a shorter one.
    """
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        engine.submit(Request(id=i, prompt=list(p), max_new_tokens=max_new))
    done = engine.run()
    wall = time.perf_counter() - t0
    if engine.rejected or engine.unfinished or len(done) != len(prompts):
        raise RuntimeError(
            f"{len(done)}/{len(prompts)} requests finished; rejected "
            f"{[(r.id, r.error) for r in engine.rejected]}, unfinished "
            f"{[r.id for r in engine.unfinished]}")
    return sorted(done, key=lambda r: r.id), wall


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", default="smollm-360m", choices=list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true",
                    help="serve the reduced CPU-sized config")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--max-rows", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=2048)
    args = ap.parse_args()

    setup_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.is_encoder_decoder or cfg.n_image_tokens:
        print(f"[serve] note: {args.arch} needs frontend embeddings; "
              "serving text-only decoder path")
    eng = PagedServingEngine(cfg, max_rows=args.max_rows,
                             max_len=args.max_len, seed=args.seed,
                             **ENGINE_SHAPE)
    prompts = synth_prompts(cfg.vocab_size, args.requests, args.max_len,
                            args.seed)
    done, wall = serve(eng, prompts, args.max_new)
    dev = jax.devices()[0]
    toks = sum(len(r.out_tokens) for r in done)
    print(f"[serve] {cfg.name} on {dev.platform} ({dev.device_kind}): "
          f"{len(done)} requests, {toks} tokens in {wall:.2f}s "
          f"(first run, compilation included)")
    for r in done[:3]:
        print(f"  req {r.id}: {r.out_tokens}")


if __name__ == "__main__":
    main()
