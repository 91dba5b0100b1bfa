"""Pipeline-parallel microservice serving executors (dense + paged).

`microservice.partition.decompose` turns a model into light services
plus N core stages over contiguous layer ranges; until now those specs
only fed the *planning* side (static IP + Lyapunov controller) while
``ServingEngine`` executed every model monolithically.  This module
closes the profile→place→execute loop:

  1. each core stage becomes a sub-executor owning **only** its layer
     range's parameter slice and cache slice
     (:meth:`repro.models.model.Model.stage_params` /
     ``init_cache(layers=...)`` — or, for the paged executor, the layer
     range's slice of the shared block pools,
     :meth:`repro.models.kvcache.PagedCache.struct`);
  2. activations hand off between stages through a network shim whose
     per-hop latency/bandwidth comes from a ``core.network.EdgeNetwork``
     and a stage→node placement — a ``static_placement`` solution
     directly determines where each stage "runs" and what transfer cost
     it pays;
  3. measured per-stage latencies (:meth:`PipelinedEngine.profile`)
     feed back into ``partition.to_application``, so the placement is
     re-derived from the *executed* pipeline, not FLOP estimates.

Stage compute is real (jitted JAX, token-identical to the monolithic
engine — composition of ``run_stages`` over consecutive ranges
reproduces the forward op-for-op); the network is simulated (hop delays
are accounted, not slept).  Chunked prefill and profiling run one
jitted program per stage; the decode hot loop chains every stage inside
one fused, donated macro-step scan (:func:`macro_step`,
SERVING.md §The decode hot loop) while the per-hop accounting stays
per device step.  Light services are accounted at fixed homes:
tokenize/detokenize at the entry node, sample co-located with the exit
stage.

Cache layout invariants: every stage's cache slice is indexed by the
same request identity — dense engines by batch slot (each stage holds
that slot's rows for its layers), paged engines by the *engine-level*
block tables (one :class:`~repro.models.kvcache.PagedCache` ledger
governs every stage's pools, so block id ``b`` addresses the same
logical tokens in each stage's layer slice).  Admission zeroes the
request's SSM state rows and cross blocks in **every** stage.

Enc-dec configs: the ``encoder`` core stage is planning-only here, as in
``ServingEngine`` (token requests carry no frontend; decoder cross-attn
reads the zero-initialised cache), so the executor chains decoder
stages only.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import static_placement as sp
from repro.core.network import resource_index
from repro.core.qos import qos_scores
from repro.microservice.partition import (StageSpec, decompose,
                                          profile_stage_ms, to_application)
from repro.models import build_model, bytes_per_param, quantize_params
from repro.models.kvcache import (PagedCache, paged_copy_blocks,
                                  paged_reset_row)
from repro.models.model import (greedy_scan_update, greedy_verify_update,
                                row_isolated, ssm_row_isolated)
from repro.models.transformer import segment_range
from repro.serving.engine import (_PagedEngine, _SlotEngine,
                                  reset_cache_row)

PLACEMENT_STRATEGIES = ("static_ip", "colocate", "round_robin", "random")


def place_stages(app, net, strategy: str = "static_ip", *, kappa: int = 2,
                 xi: float = sp.XI_DEFAULT, horizon_slots: int = 100,
                 rng: Optional[np.random.Generator] = None,
                 bytes_per_param: Optional[float] = None
                 ) -> Dict[str, int]:
    """Map each core service of ``app`` to a network node.

    ``static_ip`` solves the paper's sparsity-constrained integer
    program (eq. 14, C4–C6) over QoS scores and picks each stage's
    most-instantiated site; the rest are baselines for the bench.
    """
    core = app.core_ids
    es = [int(v) for v in np.flatnonzero(net.is_es)]
    es = es or list(range(net.n_nodes))
    if strategy == "static_ip":
        z, q = qos_scores(app, net)
        prob = sp.build_problem(app, net, z, q, kappa=kappa, xi=xi,
                                horizon_slots=horizon_slots,
                                bytes_per_param=bytes_per_param)
        x = sp.solve(prob)
        return {app.ms(m).name: (int(np.argmax(x[m])) if x[m].sum() > 0
                                 else es[0]) for m in core}
    if strategy == "colocate":
        # fattest GPU among ESs — by the named resource column, falling
        # back to total capacity when R is narrower than Table I's
        # [CPU, RAM, GPU, VRAM] layout
        gpu = resource_index("gpu")
        if net.R.shape[1] > gpu:
            score = net.R[es, gpu]
        else:
            score = net.R[es].sum(axis=1)
        v = es[int(np.argmax(score))]
        return {app.ms(m).name: v for m in core}
    if strategy == "round_robin":
        return {app.ms(m).name: es[i % len(es)] for i, m in enumerate(core)}
    if strategy == "random":
        rng = rng if rng is not None else np.random.default_rng(0)
        return {app.ms(m).name: int(rng.choice(es)) for m in core}
    raise ValueError(f"unknown placement strategy {strategy!r}; "
                     f"known: {PLACEMENT_STRATEGIES}")


def macro_step(model, ranges: List[Tuple[int, int]], k: int):
    """The pipelined engine's fused K-step decode, unjitted: one
    ``lax.scan`` whose body runs the layer ``ranges`` in order, then
    greedy argmax / token feedback / pos bump / budget masking.  Takes
    ``(params_list, caches_list, tok, pos, budget, pmeta=None)`` with one
    params / caches entry per range; returns ``(tokens (B, k),
    caches_list)``."""
    vocab = model.cfg.vocab_size

    def run(params_list, caches_list, tok, pos, budget, pmeta=None):
        def body(carry, _):
            caches_list, tok, pos, budget = carry
            x = tok
            new_list = []
            for p, c, (lo, hi) in zip(params_list, caches_list, ranges):
                x, nc, _ = model.run_stages(
                    p, x, lo, hi, mode="decode", pos=pos, caches=c,
                    paged=pmeta)
                new_list.append(nc)
            tok, pos, budget, emit = greedy_scan_update(
                x, pos, budget, vocab)
            return (new_list, tok, pos, budget), emit

        carry = (caches_list, tok, pos, budget)
        (caches_list, _, _, _), toks = jax.lax.scan(
            body, carry, None, length=k)
        return jnp.transpose(toks), caches_list

    return run


class _CoreStage:
    """One sub-executor: layers [lo, hi), its param/cache slices, and
    jitted chunked-prefill / row-reset / per-stage decode programs.

    With ``paged`` set (a :class:`~repro.models.kvcache.PagedCache`),
    the stage's caches are its layer slice of the shared block pools
    and every jitted program takes the engine's block-table metadata.

    The prefill/reset jits donate their cache argument (the stage
    rebinds ``self.caches`` each call).  The per-stage ``decode`` jit is
    the *profiling* program (``PipelinedEngine.profile`` measures one
    stage at a time) and deliberately does NOT donate — profiling must
    not consume the live serving caches.  The serving decode path runs
    through the engine's fused macro-step instead
    (``_NetShimMixin._macro_jit``), which chains every stage inside one
    scan and donates the whole cache list.
    """

    def __init__(self, model, params, spec: StageSpec, *, entry: bool,
                 exit_head: bool, max_batch: int, cache_len: int,
                 paged: Optional[PagedCache] = None):
        self.spec = spec
        self.name = spec.name
        self.lo, self.hi = spec.layer_range
        self.node: int = 0
        self.paged = paged
        self.params = model.stage_params(params, self.lo, self.hi,
                                         entry=entry, exit_head=exit_head)
        # admission discards prompt logits, so prefill skips the head
        self.prefill_params = {k: v for k, v in self.params.items()
                               if k not in ("lm_head", "final_norm")}
        lo, hi = self.lo, self.hi
        segs = segment_range(model.cfg, lo, hi)

        self._jits = {}
        if paged is None:
            self.caches = model.init_cache(max_batch, cache_len,
                                           layers=(lo, hi))

            def _decode(p, caches, x, pos):
                y, new_caches, _ = model.run_stages(
                    p, x, lo, hi, mode="decode", pos=pos, caches=caches)
                return y, new_caches

            def _prefill(p, caches, x, pos0, slot):
                def run(row):
                    y, new_row, _ = model.run_stages(
                        p, x, lo, hi, mode="chunk",
                        pos=jnp.reshape(pos0, (1,)).astype(jnp.int32),
                        caches=row)
                    return y, new_row
                return row_isolated(run, caches, slot)

            self._jits["reset"] = jax.jit(reset_cache_row,
                                          donate_argnums=(0,))
        else:
            self.caches = paged.struct(model.dtype, layers=(lo, hi))

            def _decode(p, caches, x, pos, pmeta):
                y, new_caches, _ = model.run_stages(
                    p, x, lo, hi, mode="decode", pos=pos, caches=caches,
                    paged=pmeta)
                return y, new_caches

            def _prefill(p, caches, x, pos0, row, pmeta):
                def run(c):
                    y, new_c, _ = model.run_stages(
                        p, x, lo, hi, mode="chunk",
                        pos=jnp.reshape(pos0, (1,)).astype(jnp.int32),
                        caches=c, paged=pmeta)
                    return y, new_c
                return ssm_row_isolated(run, segs, caches, row)

            self._jits["reset"] = jax.jit(
                lambda caches, row, xids: paged_reset_row(caches, segs,
                                                          row, xids),
                donate_argnums=(0,))
            has_swa = paged.has_swa
            self._jits["cow"] = jax.jit(
                lambda caches, src, dst: paged_copy_blocks(
                    caches, segs, src, dst, has_swa=has_swa),
                donate_argnums=(0,))

        # reprolint: disable-next=jit-donation -- profile-only jit:
        # profile() must not consume the live serving caches (PR 5)
        self._jits["decode"] = jax.jit(_decode)
        self._jits["prefill"] = jax.jit(_prefill, donate_argnums=(1,))

    def prefill(self, x, pos0, slot, pmeta=None):
        args = (() if self.paged is None else (pmeta,))
        x, self.caches = self._jits["prefill"](
            self.prefill_params, self.caches, x, pos0, slot, *args)
        return x

    def reset_row(self, slot, xids=None):
        args = (() if self.paged is None else (xids,))
        self.caches = self._jits["reset"](self.caches, slot, *args)

    def copy_blocks(self, src, dst):
        """COW pool copies on this stage's layer slice of the pools."""
        self.caches = self._jits["cow"](self.caches, src, dst)


class _NetShimMixin:
    """Placement, profiling, and simulated-network accounting shared by
    the dense and paged pipelined engines (the profile→place→execute
    loop).  Simulated-network stats accumulate in :attr:`transfer_ms` /
    :attr:`transfer_mb` / :attr:`hops` (keyed ``(src_node, dst_node)``).
    """

    def _init_stages_and_net(self, cfg, params, *, n_stages, max_batch,
                             cache_len, seed, net, placement, entry_node,
                             paged: Optional[PagedCache] = None,
                             quantization=None):
        assert 1 <= n_stages <= cfg.n_layers, (n_stages, cfg.n_layers)
        self.model = build_model(cfg, qformat=quantization)
        self.quantization = self.model.qformat
        key = jax.random.PRNGKey(seed)
        self.params = params if params is not None else self.model.init(key)
        # pack projection weights BEFORE stage construction so every
        # stage's slice_blocks slice carries the packed leaves; static
        # non-donated jit operands, same contract as the monolithic
        # engines (reprolint quant-static-weights)
        self.params = quantize_params(self.params, self.quantization)
        self.batch_width = max_batch

        # stage service sizes reflect the *resident* weight format, so
        # profile->place->execute sees the quantized footprint
        self.stage_specs: List[StageSpec] = decompose(
            cfg, n_core_stages=n_stages,
            bytes_per_param=bytes_per_param(self.quantization))
        decoder = [s for s in self.stage_specs
                   if s.kind == "core" and s.name != "encoder"]
        self.stages = [
            _CoreStage(self.model, self.params, spec,
                       entry=(i == 0), exit_head=(i == len(decoder) - 1),
                       max_batch=max_batch, cache_len=cache_len,
                       paged=paged)
            for i, spec in enumerate(decoder)]

        self.net = net
        self.entry_node = (entry_node if entry_node is not None
                           else (int(net.user_ed[0]) if net is not None
                                 else 0))
        if placement:
            self.set_placement(placement)
        self._act_bytes = jnp.dtype(cfg.dtype).itemsize * cfg.d_model
        self.transfer_ms = 0.0
        self.transfer_mb = 0.0
        self.hops: Dict[tuple, dict] = {}

    # ------------------------------------------------------------------
    # placement / profiling (the profile→place→execute loop)
    # ------------------------------------------------------------------
    def set_placement(self, placement: Dict[str, int]):
        """Pin each stage to a node (unnamed stages keep their node)."""
        for st in self.stages:
            if st.name in placement:
                st.node = int(placement[st.name])

    @property
    def placement(self) -> Dict[str, int]:
        return {st.name: st.node for st in self.stages}

    def profile(self, iters: int = 3) -> Dict[str, float]:
        """Measured per-stage decode latency (ms) via
        ``partition.profile_stage_ms`` — feed to :meth:`to_application`.
        Uses the per-stage (non-donating) decode jits, so profiling
        leaves the live serving caches untouched."""
        out = {}
        pos = jnp.zeros((self.batch_width,), jnp.int32)
        meta = self.pc.meta() if hasattr(self, "pc") else None
        for i, st in enumerate(self.stages):
            if i == 0:
                x = jnp.zeros((self.batch_width, 1), jnp.int32)
            else:
                x = jnp.zeros((self.batch_width, 1, self.cfg.d_model),
                              jnp.dtype(self.cfg.dtype))
            if meta is None:
                fn = (lambda xx=x, ss=st:
                      ss._jits["decode"](ss.params, ss.caches, xx, pos)[0])
            else:
                fn = (lambda xx=x, ss=st:
                      ss._jits["decode"](ss.params, ss.caches, xx, pos,
                                         meta)[0])
            out[st.name] = profile_stage_ms(fn, iters=iters)
        return out

    def to_application(self, rng: np.random.Generator,
                       measured_ms: Optional[Dict[str, float]] = None,
                       **kwargs):
        """Bridge the executed pipeline back to the paper abstraction."""
        return to_application(self.cfg, self.stage_specs, rng,
                              measured_ms=measured_ms, **kwargs)

    # ------------------------------------------------------------------
    # fused macro-step: every stage chained inside one jitted scan
    # ------------------------------------------------------------------
    def _macro_jit(self, k: int):
        """Fused K-step decode across all stages: one ``lax.scan`` whose
        body chains the stage layer ranges (composition reproduces the
        monolithic forward op-for-op), then does argmax / token feedback
        / pos bump / budget masking on device — the pipelined analogue
        of ``Model.decode_steps``.  The per-stage cache list is the scan
        carry and is donated; the per-hop *network* accounting stays on
        the host (:meth:`_account_macro`), priced per device step as
        before — fusing the stages into one program changes where the
        Python process computes, not what the simulated network ships.
        """
        key = f"decode{k}"
        if key not in self._jits:
            run = macro_step(self.model,
                             [(st.lo, st.hi) for st in self.stages], k)
            self._jits[key] = jax.jit(run, donate_argnums=(1,))
        return self._jits[key]

    def _run_macro(self, tokens: np.ndarray, pos: np.ndarray,
                   budgets: np.ndarray, k: int, pmeta=None) -> np.ndarray:
        """Invoke the fused macro-step, rebind every stage's caches
        (they were donated), and account the per-step network hops."""
        params_list = [st.params for st in self.stages]
        caches_list = [st.caches for st in self.stages]
        args = (() if pmeta is None else (pmeta,))
        toks, new_caches = self._macro_jit(k)(
            params_list, caches_list, jnp.asarray(tokens),
            jnp.asarray(pos), jnp.asarray(budgets), *args)
        for st, nc in zip(self.stages, new_caches):
            st.caches = nc
        self._account_macro(budgets, k)
        # reprolint: disable-next=host-sync -- the ONE deliberate sync
        # per macro-step (counted in n_host_syncs; <= 1/K per token)
        return np.asarray(toks)

    def _account_macro(self, budgets: np.ndarray, k: int):
        """Simulated-network accounting for one macro-step: device step
        i ships for the rows still live at that step (budget > i) — the
        same per-token hop pattern the per-token loop produced: token
        ids entry->stage0, activations between stages, the sampled
        token id back to the entry node for detokenize."""
        for i in range(k):
            n = int((budgets > i).sum())
            if n == 0:
                break
            self._ship(self.entry_node, self.stages[0].node, n * 4 / 1e6)
            for kk in range(len(self.stages)):
                self._ship_between(kk, n, self._act_bytes)
            self._ship(self.stages[-1].node, self.entry_node, n * 4 / 1e6)

    # ------------------------------------------------------------------
    # fused draft-verify round: every stage chained inside one jitted
    # chunk forward (the pipelined analogue of ``Model.verify_steps``)
    # ------------------------------------------------------------------
    def _verify_chain_jit(self, s: int):
        """Fused verification of an (B, S) draft chunk across all
        stages: one teacher-forced chunk forward chained through the
        stage layer ranges (composition reproduces the monolithic
        ``verify_steps`` op-for-op), then the greedy accept/emit mask
        on device.  Named ``_verify_chain_jit`` (not ``_verify_jit``)
        because ``_EngineBase._verify_jit`` wins the MRO and routes
        monolithic models — the engines' ``_forward_verify`` below
        calls this chain directly."""
        key = f"verify{s}"
        if key not in self._jits:
            model = self.model
            ranges = [(st.lo, st.hi) for st in self.stages]
            vocab = self.cfg.vocab_size

            def run(params_list, caches_list, tok, pos, budget,
                    pmeta=None):
                x = tok
                new_list = []
                for p, c, (lo, hi) in zip(params_list, caches_list,
                                          ranges):
                    x, nc, _ = model.run_stages(
                        p, x, lo, hi, mode="chunk", pos=pos,
                        caches=c, paged=pmeta)
                    new_list.append(nc)
                emit = greedy_verify_update(x, tok, budget, vocab)
                return emit, new_list

            self._jits[key] = jax.jit(run, donate_argnums=(1,))
        return self._jits[key]

    def _run_verify(self, tokens: np.ndarray, pos: np.ndarray,
                    budgets: np.ndarray, pmeta=None) -> np.ndarray:
        """Invoke the fused verify round, rebind every stage's caches
        (they were donated), and account the per-round network hops."""
        params_list = [st.params for st in self.stages]
        caches_list = [st.caches for st in self.stages]
        args = (() if pmeta is None else (pmeta,))
        emit, new_caches = self._verify_chain_jit(tokens.shape[1])(
            params_list, caches_list, jnp.asarray(tokens),
            jnp.asarray(pos), jnp.asarray(budgets), *args)
        for st, nc in zip(self.stages, new_caches):
            st.caches = nc
        self._account_verify(budgets, tokens.shape[1])
        # reprolint: disable-next=host-sync -- the ONE deliberate sync
        # per verify round (counted in n_host_syncs; <= 1 per token)
        return np.asarray(emit)

    def _account_verify(self, budgets: np.ndarray, s: int):
        """Simulated-network accounting for one verify round: every
        live row ships its whole (K+1)-token chunk at once — draft ids
        entry->stage0, chunk activations between stages, emitted ids
        back for detokenize.  One hop per round instead of one per
        token is the speculative latency win on the wire."""
        n = int((budgets > 0).sum())
        if n == 0:
            return
        self._ship(self.entry_node, self.stages[0].node, n * s * 4 / 1e6)
        for kk in range(len(self.stages)):
            self._ship_between(kk, n * s, self._act_bytes)
        self._ship(self.stages[-1].node, self.entry_node, n * s * 4 / 1e6)

    # ------------------------------------------------------------------
    # network shim
    # ------------------------------------------------------------------
    def _ship(self, src: int, dst: int, mb: float):
        if self.net is None or src == dst or mb <= 0.0:
            return
        ms = self.net.path_ms(src, dst, mb)
        self.transfer_ms += ms
        self.transfer_mb += mb
        agg = self.hops.setdefault((src, dst),
                                   {"count": 0, "mb": 0.0, "ms": 0.0})
        agg["count"] += 1
        agg["mb"] += mb
        agg["ms"] += ms

    def _ship_between(self, k: int, n: int, per_token_bytes: float):
        if k + 1 < len(self.stages):
            self._ship(self.stages[k].node, self.stages[k + 1].node,
                       n * per_token_bytes / 1e6)


class PipelinedEngine(_SlotEngine, _NetShimMixin):
    """Continuous-batching engine whose forward pass is split across
    placed core stages.  API mirrors :class:`ServingEngine` (both share
    the :class:`_SlotEngine` state machine); greedy outputs are
    token-identical to it (tests/test_pipeline.py)."""

    def __init__(self, cfg, params=None, *, n_stages: int = 2,
                 max_batch: int = 4, cache_len: int = 128, seed: int = 0,
                 prefill_chunk: int = 16, net=None,
                 placement: Optional[Dict[str, int]] = None,
                 entry_node: Optional[int] = None, decode_steps: int = 1,
                 policy=None, speculative=None, quantization=None):
        super().__init__(cfg, max_batch=max_batch, cache_len=cache_len,
                         prefill_chunk=prefill_chunk,
                         decode_steps=decode_steps, policy=policy,
                         speculative=speculative)
        self._init_stages_and_net(cfg, params, n_stages=n_stages,
                                  max_batch=max_batch, cache_len=cache_len,
                                  seed=seed, net=net, placement=placement,
                                  entry_node=entry_node,
                                  quantization=quantization)

    # ------------------------------------------------------------------
    # _SlotEngine hooks
    # ------------------------------------------------------------------
    def _reset_row(self, slot: int):
        s = jnp.int32(slot)
        for st in self.stages:
            st.reset_row(s)

    def _prefill_row(self, slot: int, toks: np.ndarray, pos0: int):
        c = len(toks)
        x = jnp.asarray(toks[None])
        p0, sl = jnp.int32(pos0), jnp.int32(slot)
        self._ship(self.entry_node, self.stages[0].node, c * 4 / 1e6)
        for k, st in enumerate(self.stages):
            x = st.prefill(x, p0, sl)
            self._ship_between(k, c, self._act_bytes)

    def _forward_steps(self, tokens: np.ndarray, pos: np.ndarray,
                       budgets: np.ndarray, k: int) -> np.ndarray:
        return self._run_macro(tokens, pos, budgets, k)

    def _forward_verify(self, tokens: np.ndarray, pos: np.ndarray,
                        budgets: np.ndarray) -> np.ndarray:
        return self._run_verify(tokens, pos, budgets)


class PagedPipelinedEngine(_PagedEngine, _NetShimMixin):
    """Paged continuous-batching engine over placed core stages: the
    block-granular scheduler of :class:`_PagedEngine` with the stage
    executor + network shim of :class:`PipelinedEngine`.  One
    engine-level :class:`~repro.models.kvcache.PagedCache` ledger
    governs every stage's layer-sliced pools, so admission, growth,
    and preemption decisions apply to the whole pipeline at once.
    Greedy outputs are token-identical to the dense engines
    (tests/test_paged.py)."""

    def __init__(self, cfg, params=None, *, n_stages: int = 2,
                 max_rows: int = 8, max_len: int = 128,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 seed: int = 0, prefill_chunk: int = 16,
                 watermark_blocks: int = 0, net=None,
                 placement: Optional[Dict[str, int]] = None,
                 entry_node: Optional[int] = None, decode_steps: int = 1,
                 policy=None, prefix_sharing: bool = True,
                 speculative=None, quantization=None):
        super().__init__(cfg, max_rows=max_rows, max_len=max_len,
                         block_size=block_size, num_blocks=num_blocks,
                         prefill_chunk=prefill_chunk,
                         watermark_blocks=watermark_blocks,
                         decode_steps=decode_steps, policy=policy,
                         prefix_sharing=prefix_sharing,
                         speculative=speculative)
        self._init_stages_and_net(cfg, params, n_stages=n_stages,
                                  max_batch=max_rows, cache_len=max_len,
                                  seed=seed, net=net, placement=placement,
                                  entry_node=entry_node, paged=self.pc,
                                  quantization=quantization)

    # ------------------------------------------------------------------
    # _PagedEngine hooks
    # ------------------------------------------------------------------
    def _reset_row(self, row: int):
        r = jnp.int32(row)
        xids = jnp.asarray(self.pc.cross_tables[row].copy())
        for st in self.stages:
            st.reset_row(r, xids)

    def _prefill_row(self, row: int, toks: np.ndarray, pos0: int):
        c = len(toks)
        x = jnp.asarray(toks[None])
        p0, r = jnp.int32(pos0), jnp.int32(row)
        meta = self.pc.meta(row=row)
        self._ship(self.entry_node, self.stages[0].node, c * 4 / 1e6)
        for k, st in enumerate(self.stages):
            x = st.prefill(x, p0, r, meta)
            self._ship_between(k, c, self._act_bytes)

    def _apply_cow(self, pairs):
        src = jnp.asarray([s for s, _ in pairs], jnp.int32)
        dst = jnp.asarray([d for _, d in pairs], jnp.int32)
        for st in self.stages:
            st.copy_blocks(src, dst)

    def _forward_steps(self, tokens: np.ndarray, pos: np.ndarray,
                       budgets: np.ndarray, k: int) -> np.ndarray:
        return self._run_macro(tokens, pos, budgets, k,
                               pmeta=self.pc.meta())

    def _forward_verify(self, tokens: np.ndarray, pos: np.ndarray,
                        budgets: np.ndarray) -> np.ndarray:
        return self._run_verify(tokens, pos, budgets,
                                pmeta=self.pc.meta())
