"""The correctness check separates the program from its control and from
a broken timed path: a run with the program sound is correct; the
float8 control, put in the program's place, is not; and a window driven
with the engine broken underneath comes out not correct, for each fault
a serving cell can have (its state left unchanged, a token altered where
it is produced, half of the batch left out).

The windows arrive as one burst, so that every decode row is in use
whatever the speed of the host: half a batch can only be left out of a
window that fills more than one row."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import harness
from chipbench.tests import tiny

SEED = 2**31 + 99


BURST = {"kind": "square", "period_s": 1.5, "high_s": 0.05,
         "high": 1000.0, "low": 0.001}


@pytest.fixture(scope="module", params=("llama", "mamba1"))
def cell(request):
    spec = tiny.spec(request.param, rate=5.0)
    spec["traffic"]["arrivals"] = BURST
    setup = harness.build(spec, SEED)
    harness.warm(setup, 1)
    return setup


def window(setup, seed=SEED):
    run = harness.measure(setup, seed, 1.5, trace=False, t_start=0.0)
    assert max(s.rows for s in run.window.steps) >= 2
    return run, harness.correctness(setup, run, seed)


def test_sound_program_is_correct_and_control_is_not(cell):
    run, got = window(cell)
    assert got["correct"], got["checks"]
    limit = cell.spec["config"]["check"]["logit_gap_max"]
    assert got["logit_gap_max"] < limit / 3
    ctrl = harness.correctness(cell, run, SEED, control=True)
    assert not ctrl["correct"], ctrl["checks"]
    assert ctrl["checks"]["logit_gap_max"]["value"] > limit
    assert ctrl["program_gap_max"] == got["logit_gap_max"]


def _state_unchanged(fn, vocab):
    def broken(params, caches, *rest):
        keep = jax.tree.map(jnp.copy, caches)
        out, _ = fn(params, caches, *rest)
        return out, keep
    return broken


def _token_altered(fn, vocab):
    def broken(*args):
        toks, caches = fn(*args)
        first = toks[:, 0]
        return toks.at[:, 0].set(jnp.where(first >= 0, (first + 1) % vocab,
                                           first)), caches
    return broken


def _half_batch(fn, vocab):
    def broken(*args):
        toks, caches = fn(*args)
        odd = (jnp.arange(toks.shape[0]) % 2 == 1)[:, None]
        return jnp.where(odd & (toks >= 0), 1, toks), caches
    return broken


FAULTS = {
    "decode_state_unchanged": ("decode", _state_unchanged),
    "prefill_state_unchanged": ("prefill", _state_unchanged),
    "token_altered": ("decode", _token_altered),
    "half_batch_left_out": ("decode", _half_batch),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(cell, fault):
    prog, make = FAULTS[fault]
    jits = cell.engine._jits
    saved = dict(jits)
    vocab = cell.mcfg.vocab_size
    for name, fn in saved.items():
        if name.startswith(prog):
            dict.__setitem__(jits, name, make(fn, vocab))
    try:
        _, got = window(cell)
    finally:
        for name, fn in saved.items():
            dict.__setitem__(jits, name, fn)
    assert not got["correct"], got["checks"]
