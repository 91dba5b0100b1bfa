"""The serving entry point (launch/serve.py) and chip_smoke.py on the CPU:
the same functions the chip run calls, at the reduced config."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_smoke_config
from repro.launch import serve as serve_mod
from repro.launch.serve import ENGINE_SHAPE, serve, synth_prompts
from repro.serving.engine import PagedServingEngine

ROOT = Path(__file__).resolve().parents[1]


def test_serve_smoke_config_finishes_every_request():
    cfg = get_smoke_config("smollm-360m")
    eng = PagedServingEngine(cfg, max_rows=4, max_len=128, **ENGINE_SHAPE)
    prompts = synth_prompts(cfg.vocab_size, 6, 128, seed=0)
    done, wall = serve(eng, prompts, max_new=8)
    assert [r.id for r in done] == list(range(6))
    assert all(len(r.out_tokens) == 8 for r in done)
    assert all(r.prompt == p for r, p in zip(done, prompts))
    assert wall > 0 and not eng.rejected and not eng.unfinished


def test_serve_fails_when_a_request_cannot_finish():
    cfg = get_smoke_config("smollm-360m")
    eng = PagedServingEngine(cfg, max_rows=2, max_len=32, **ENGINE_SHAPE)
    with pytest.raises(RuntimeError, match="1/2 requests finished"):
        serve(eng, [[1, 2, 3], list(range(1, 31))], max_new=4)


def test_synth_prompts_sizes_and_shared_prefix():
    prompts = synth_prompts(512, 16, 2048, seed=3)
    assert prompts == synth_prompts(512, 16, 2048, seed=3)
    assert all(64 <= len(p) <= 1024 for p in prompts)
    assert all(0 < t < 512 for p in prompts for t in p)
    prefix = prompts[0][:256]
    assert all(p[:256] == prefix and len(p) > 256 for p in prompts[::2])
    assert not any(p[:256] == prefix for p in prompts[1::2])


def test_compile_cache_env_wins_else_fixed_repo_path(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    try:
        assert serve_mod.setup_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = serve_mod.setup_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_phases_on_cpu(capsys):
    """Rehearsal of chip_smoke.py's phases 3-6 at the reduced config:
    paper loop, both engines, first-token and compile checks."""
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    chip_smoke.run(get_smoke_config("smollm-360m"), seed=0, n_requests=6,
                   max_new=8, max_rows=4, max_len=128, profile_batch=(2, 32))
    out = capsys.readouterr().out
    assert "paged: first token = forward argmax for 6/6" in out
    assert "paged vs pipelined: 48/48 tokens agree" in out
