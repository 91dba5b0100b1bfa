"""End-to-end driver (the paper's kind is SERVING): serve a small model
with batched requests, where

  1. the model is decomposed into core/light microservices
     (repro.microservice),
  2. stage latencies are MEASURED from the real jit'd model on this host,
  3. the paper's static placement + effective-capacity Lyapunov
     controller schedule those microservices on a simulated edge network,
  4. and the same model actually serves the token traffic through the
     continuous-batching engine.

  PYTHONPATH=src python examples/serve_batched.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs import get_smoke_config
from repro.launch.serve import paper_loop
from repro.models import build_model
from repro.serving.engine import Request, ServingEngine


def main():
    cfg = get_smoke_config("smollm-360m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    # ---- 1-3: decompose, profile real stage latencies, and let the
    # paper machinery schedule the microservices -----------------------
    loop = paper_loop(cfg, model, params, seed=0, profile_batch=(4, 32),
                      horizon_slots=40, drain_slots=300)
    print("measured stage latencies (ms):",
          {k: round(v, 2) for k, v in loop.stage_ms.items()})
    print("placement:", {loop.app.ms(mm).name: int(xv.sum())
                         for mm, xv in loop.sim.x_cr.items()})
    m = loop.metrics
    print(f"edge sim: on_time={m['on_time']:.3f} "
          f"completed={m['completed']:.3f} cost={m['total_cost']:.0f}")

    # ---- 4: actually serve batched requests ---------------------------
    eng = ServingEngine(cfg, params=params, max_batch=4, cache_len=64)
    n_req = 12
    t0 = time.perf_counter()
    for i in range(n_req):
        eng.submit(Request(id=i, prompt=[2 + i % 7, 9, 4],
                           max_new_tokens=12))
    done = eng.run()
    dt = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in done)
    print(f"served {len(done)} requests, {toks} tokens "
          f"in {dt:.2f}s ({toks/dt:.1f} tok/s on "
          f"{jax.devices()[0].platform})")


if __name__ == "__main__":
    main()
