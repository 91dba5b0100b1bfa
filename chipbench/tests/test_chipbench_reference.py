"""The float32 references against the program: ``Model.forward`` at a
reduced size in float32 (same weights, the program's own math), and the
paged engine's served tokens in bfloat16."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import bench, check, harness
from chipbench.gen.openloop import rng
from chipbench.loop import Served
from chipbench.tests import tiny

KINDS = ("llama", "mamba1")


@pytest.mark.parametrize("kind", KINDS)
def test_reference_matches_model_forward(kind):
    from repro.models import build_model
    spec = tiny.spec(kind, dtype="float32")
    config = spec["config"]
    m = config["model"]
    ref = bench.reference(config)
    w = jax.jit(functools.partial(ref.make_weights, m))(
        harness.weight_key(5))
    model = build_model(harness.model_config(config))
    toks = rng(5, 0).integers(1, m["vocab_size"], size=(1, 40))
    got = model.forward(ref.to_program(m, w),
                        {"tokens": jnp.asarray(toks)})[0][0, :,
                                                         :m["vocab_size"]]
    x = jnp.take(w["embed"], jnp.asarray(toks[0]), axis=0)
    for i in range(m["n_layers"]):
        x = ref.layer(m, w["layers"], i, x)
    from chipbench.reference import ops
    want = ops.mm(ops.rmsnorm(x, w["final_norm"], m["norm_eps"]),
                  ref.head_weight(m, w).T)[:, :m["vocab_size"]]
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-4


@pytest.mark.parametrize("kind", KINDS)
def test_reference_agrees_with_served_tokens(kind):
    """Chunked prefill into the paged cache, then macro-step decode,
    in bfloat16: every served token is the reference's top token or lies
    within rounding of it."""
    from repro.serving.engine import Request
    spec = tiny.spec(kind)
    setup = harness.build(spec, 77)
    g = rng(77, 0)
    served = []
    for i, (n, new) in enumerate([(70, 20), (33, 12), (5, 30), (100, 9)]):
        s = Served(i, 0.0, g.integers(1, 512, size=n).tolist(), new)
        s.req = Request(id=i, prompt=list(s.prompt), max_new_tokens=new)
        setup.engine.submit(s.req)
        served.append(s)
    setup.engine.run()
    for s in served:
        s.out = list(s.req.out_tokens)
        s.n_out = len(s.out)
    got = check.compare(spec["config"], setup.weights, served)
    assert got["tokens"] == 71
    assert got["logit_gap_max"] < 0.1
    assert got["top1_agree"] > 0.9
