"""Compiles for a described TPU v5e chip, with no chip attached.

The TPU compiler refuses what the chip would refuse: kernel indexing it
cannot prove aligned, ops Mosaic cannot lower, programs that overflow
HBM.  Interpret-mode tests (tests/test_kernels.py) see none of that.
Compiled here: the served path's programs at SmolLM-360M's full width
and the shapes ``chip_smoke.py`` runs, and every Pallas kernel in
``kernels/`` at the widths it would serve.  Nothing runs, so this says
nothing about results or times.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and under xdist
every worker imports this file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import (decode_attention_pallas,
                                            paged_decode_attention_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quant_matmul import quant_matmul_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.selective_scan import selective_scan_pallas
from repro.launch.serve import ENGINE_SHAPE
from repro.microservice.partition import decompose
from repro.models import build_model
from repro.models.kvcache import PagedCache
from repro.serving.pipeline import macro_step

#: HBM the v5e compiler lets one program use (its RESOURCE_EXHAUSTED
#: message reports "of 15.75G hbm")
V5E_HBM_BYTES = 15.75 * 2**30
ROWS, MAX_LEN = 8, 2048  # chip_smoke.py's engine batch


def _use_compile_cache(on: bool):
    jax.config.update("jax_enable_compilation_cache", on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    _use_compile_cache(False)
    # else the TPU library writes its compiler logs to a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        _use_compile_cache(prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    _use_compile_cache(prev)


def _on(dev, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev), tree)


@pytest.fixture(scope="module")
def smollm(one_chip):
    """Full-width SmolLM-360M: model, param and paged-cache shapes on
    the described chip, and a factory for int32 operands."""
    cfg = get_config("smollm-360m")
    model = build_model(cfg)
    pc = PagedCache(cfg, max_rows=ROWS, max_len=MAX_LEN,
                    block_size=ENGINE_SHAPE["block_size"])
    params = _on(one_chip, jax.eval_shape(model.init,
                                          jax.random.PRNGKey(0)))
    caches = _on(one_chip, jax.eval_shape(lambda: pc.struct(model.dtype)))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    return model, pc, params, caches, i32


def _macro_step(smollm):
    """The K=16 paged macro-step at 8 rows x 2048, compiled."""
    model, pc, params, caches, i32 = smollm
    k = ENGINE_SHAPE["decode_steps"]
    fn = jax.jit(functools.partial(model.decode_steps, k=k),
                 donate_argnums=(1,))
    batch = {"token": i32(ROWS, 1), "pos": i32(ROWS), "budget": i32(ROWS)}
    meta = {"tables": i32(ROWS, pc.nb_logical)}
    return fn.lower(params, caches, batch, meta).compile()


def _prefill_chunk(smollm):
    """One 256-token ``paged_prefill_chunk``, compiled."""
    model, pc, params, caches, i32 = smollm
    fn = jax.jit(model.paged_prefill_chunk, donate_argnums=(1,))
    chunk = ENGINE_SHAPE["prefill_chunk"]
    return fn.lower(params, caches, i32(1, chunk), i32(), i32(),
                    {"tables": i32(1, pc.nb_logical)}).compile()


def test_decode_macro_step_fits_v5e_hbm(smollm):
    """The K=16 paged macro-step at 8 rows x 2048 compiles, and its
    arguments plus scratch fit one chip.  The scratch is mostly the
    loops' row-major copy of the pools, converted once a call (SERVING.md
    §Donation): it grows with rows x max_len, not with K, and 64 rows x
    2048 fit (12.0 GiB)."""
    mem = _macro_step(smollm).memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, (mem.argument_size_in_bytes,
                                  mem.temp_size_in_bytes)


def test_pipelined_macro_step_fits_v5e_hbm(one_chip, smollm):
    """The two-stage pipelined engine's K=16 macro-step (both stages
    chained in one program, as chip_smoke.py serves it) at 8 rows x
    2048 compiles and fits one chip."""
    model, pc, params, _, i32 = smollm
    ranges = [s.layer_range for s in decompose(model.cfg, n_core_stages=2)
              if s.kind == "core" and s.name != "encoder"]
    params_list, caches_list = [], []
    for i, (lo, hi) in enumerate(ranges):
        params_list.append(_on(one_chip, jax.eval_shape(functools.partial(
            model.stage_params, lo=lo, hi=hi, entry=i == 0,
            exit_head=i == len(ranges) - 1), params)))
        caches_list.append(_on(one_chip, jax.eval_shape(
            lambda lo=lo, hi=hi: pc.struct(model.dtype, layers=(lo, hi)))))
    fn = jax.jit(macro_step(model, ranges, ENGINE_SHAPE["decode_steps"]),
                 donate_argnums=(1,))
    mem = fn.lower(params_list, caches_list, i32(ROWS, 1), i32(ROWS),
                   i32(ROWS), {"tables": i32(ROWS, pc.nb_logical)}
                   ).compile().memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, (mem.argument_size_in_bytes,
                                  mem.temp_size_in_bytes)


def test_paged_prefill_chunk_compiles(smollm):
    mem = _prefill_chunk(smollm).memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES


def _computations(hlo: str) -> dict:
    """Optimized HLO text -> {computation name: its instruction lines}."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _in_loops(hlo: str):
    """Instruction lines of every ``while`` body, with the fusions and
    nested loops those bodies call."""
    comps = _computations(hlo)
    todo = [b for lines in comps.values() for line in lines
            if " while(" in line
            for b in re.findall(r"body=%?([\w.\-]+)", line)]
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            todo += re.findall(
                r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", line)
            yield line


#: (program, limit on its compiled scratch in bytes or None).  The
#: K=16 macro-step's scratch was 6,512,488,960 B while each layer
#: sliced its pool out and stacked it back.
POOL_PROGRAMS = {"decode_macro_step": (_macro_step, 2.5 * 2**30),
                 "prefill_chunk": (_prefill_chunk, None)}


@pytest.mark.parametrize("program", sorted(POOL_PROGRAMS))
def test_loops_write_pools_in_place(smollm, program):
    """No loop body of the compiled program copies, slices or updates
    a whole K/V pool or one layer of it: the layer scan carries the
    stacked pools and each layer scatters and gathers its own slots
    (``transformer.apply_segments``).  The pool's entry and exit
    layout copies, once per call, sit outside the loops."""
    model, _, _, caches, _ = smollm
    compile_fn, temp_limit = POOL_PROGRAMS[program]
    compiled = compile_fn(smollm)
    stored = caches[0]["k"].shape                  # (L, NB, bs, KV*hd)
    heads = (model.cfg.n_kv_heads, model.cfg.head_dim)
    shapes = {s for full in (stored, (*stored[:-1], *heads))
              for s in (full, (1, *full[1:]), full[1:])}
    pat = re.compile(r"= bf16\[([\d,]+)\]\{[^}]*\} "
                     r"(copy|dynamic-slice|dynamic-update-slice)\(")
    hits = [line.strip()[:160] for line in _in_loops(compiled.as_text())
            if (m := pat.search(line))
            and tuple(map(int, m.group(1).split(","))) in shapes]
    assert not hits, hits
    if temp_limit is not None:
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < temp_limit, temp


# SmolLM-360M widths: 15 query / 5 KV heads of 64, d_model 960, d_ff
# 2560, 8 decode rows over 2048 positions in 16-token blocks (1024 pool
# blocks + scratch); the paged decode kernel reads the 32 layers'
# stacked pool, whose 320-wide slots are stored 384 wide.  The
# selective scan takes Falcon-Mamba-7B's d_inner 8192 and d_state 16
# over a 256-token prefill chunk, in f32 as models/ssm.py feeds it.
_BF, _F32, _I32 = jnp.bfloat16, jnp.float32, jnp.int32
KERNELS = {
    "flash_attention": (flash_attention_pallas, [
        ((1, 15, 512, 64), _BF), ((1, 5, 512, 64), _BF),
        ((1, 5, 512, 64), _BF)]),
    "decode_attention": (decode_attention_pallas, [
        ((8, 15, 64), _BF), ((8, 5, 2048, 64), _BF),
        ((8, 5, 2048, 64), _BF), ((8,), _I32)]),
    "paged_decode_attention": (functools.partial(
        paged_decode_attention_pallas, kv_heads=5), [
        ((8, 15, 64), _BF), ((32, 1025, 16, 384), _BF),
        ((32, 1025, 16, 384), _BF), ((8, 128), _I32), ((8,), _I32),
        ((), _I32)]),
    "rmsnorm": (rmsnorm_pallas, [((8, 960), _BF), ((960,), _BF)]),
    "quant_matmul_int8": (quant_matmul_pallas, [
        ((8, 960), _BF), ((960, 2560), jnp.int8), ((1, 2560), _F32)]),
    "quant_matmul_int4": (quant_matmul_pallas, [
        ((8, 960), _BF), ((480, 2560), jnp.uint8), ((15, 2560), _F32)]),
    "selective_scan": (selective_scan_pallas, [
        ((1, 256, 8192), _F32), ((1, 256, 16), _F32),
        ((1, 256, 16), _F32), ((1, 256, 8192), _F32),
        ((8192, 16), _F32), ((1, 8192, 16), _F32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    kernel, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    fn = jax.jit(functools.partial(kernel, interpret=False))
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()
