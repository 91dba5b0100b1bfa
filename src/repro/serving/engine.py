"""Serving engines: dense slot-based and paged continuous batching.

Two admission disciplines share the chunked-prefill + batched greedy
decode machinery (SERVING.md walks the full request lifecycle):

* **Dense** (:class:`_SlotEngine` → :class:`ServingEngine`) — a fixed
  decode batch with one full ``cache_len`` KV row per slot; a request
  is admitted only when a whole slot frees, so memory is reserved
  worst-case and mixed-length workloads strand most of it.
* **Paged** (:class:`_PagedEngine` → :class:`PagedServingEngine`) — a
  block pool + per-request block tables
  (:class:`repro.models.kvcache.PagedCache`); admission is token-level
  (admit whenever enough free blocks exist), blocks are allocated as
  sequences grow and freed on completion, and when the pool is
  exhausted the newest request is **preempted by recompute**: its
  blocks are freed and it re-queues with its generated prefix, which
  re-prefills on re-admission — greedy decode makes the continuation
  token-identical, so preemption is invisible in outputs.

Cache layout invariants both engines rely on (see also
`src/repro/models/kvcache.py`): prefill/decode touch only the admitted
request's cache rows/blocks; stale attention KV is masked by position
but SSM recurrent/conv state is **not**, so the request's SSM state row
(and its cross-KV blocks, which are read unmasked) must be zeroed at
admission; chunked prefill processes ``prefill_chunk`` prompt tokens
per jitted call with power-of-two tails (:func:`chunk_sizes`) to bound
compiled program shapes.

Both state machines live here and are shared with the pipeline-parallel
executors (serving/pipeline.py); subclasses supply
``_reset_row`` / ``_prefill_row`` / ``_forward_steps``.

The decode hot loop is **device-resident** (SERVING.md §The decode hot
loop): every engine iteration runs one fused *macro-step* — a single
jitted ``lax.scan`` of up to ``decode_steps`` (K) greedy decode
iterations (``Model.decode_steps``) that does argmax-over-logical-
vocab, token feedback, per-row ``pos`` bumps, and per-row done masking
on device, returning only ``(rows, K)`` int32 token ids.  The host
syncs once per macro-step instead of once per token and never sees
logits; admission, block growth, and preemption re-enter only at
macro-step boundaries, with each row's in-scan step *budget* clamped so
``max_new_tokens``, cache headroom, and block coverage can never be
violated mid-scan.  Greedy token streams are identical for every K —
outside the pre-existing MoE co-batch carve-out (SERVING.md): under
expert-capacity pressure any change in admission *timing* (macro
boundaries included) changes what a request is co-batched with.
All decode/prefill/reset jits **donate** their cache argument — the
engine treats caches as linear state (every call rebinds
``self.caches`` to the returned pytree and never touches the donated
input again), so XLA reuses the cache buffers in place across steps.
Jitted callables live in ``self._jits`` (name -> callable) so
`serving/instrument.py` can count dispatches without touching engine
code.

Engine time is a **step counter** (one :meth:`step` = one decode
iteration): ``Request.t_submit`` / ``t_admit`` / ``t_done`` are stamped
in those units, so queueing delay (``t_admit - t_submit``) and
completion latency (``t_done - t_submit``) are comparable across
engines (benchmarks/paged_bench.py reports both).  Requests that can
never be served (prompt + max_new_tokens over capacity) are rejected
with ``Request.error`` set — they land in ``engine.rejected``, never
killing the engine.

The step counter is the only clock the engine decides by.  Its spans
and request marks (``self.trace``: an ``EngineTrace`` attached by
`serving/instrument.py`, else a null trace that records nothing) are
stamped on a clock the *caller* injects, so no wall-clock read lives
here, and the trace only records: no admission, growth, preemption or
scan-length decision reads it.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import build_model, quantize_params
from repro.models.kvcache import (PagedCache, paged_copy_blocks,
                                  paged_reset_row)
from repro.serving.instrument import NULL_TRACE
from repro.serving.scheduler import (DEFER, REJECT, CapacityView,
                                     make_policy)
from repro.serving.speculative import SpecConfig, spec_supported


def chunk_sizes(n: int, chunk: int) -> List[int]:
    """Split a prefill of n tokens into jit-friendly chunk lengths:
    full ``chunk``-sized pieces, then a power-of-two decomposition of
    the remainder — so at most log2(chunk) distinct program shapes ever
    compile, whatever prompt lengths arrive."""
    out = [chunk] * (n // chunk)
    rem, bit = n % chunk, 1
    tail: List[int] = []
    while rem:
        if rem & 1:
            tail.append(bit)
        bit <<= 1
        rem >>= 1
    return out + tail[::-1]


def reset_cache_row(caches, slot):
    """Zero batch row ``slot`` of a cache pytree (leaves are
    (n_layers, batch, ...)).  Jit this once per engine."""
    return jax.tree.map(lambda a: a.at[:, slot].set(0), caches)


@dataclass
class Request:
    """One generation request.  ``t_*`` are engine step-counter stamps
    (:meth:`_SlotEngine.step` iterations): ``t_submit`` on submit
    (stamped once — a resubmitted / resumed request keeps the
    original), ``t_admit`` on *first* admission (preemption keeps the
    original), ``t_first`` at the device step the first output token
    was produced (the TTFT stamp), ``t_done`` on completion or
    rejection.  ``qos`` names a :data:`repro.serving.scheduler.
    QOS_CLASSES` tier — deadline-driven policies read its TTFT/TPOT
    budgets; the default FIFO policy ignores it.  ``n_preempted``
    counts preempt-by-recompute evictions (policies with
    ``max_preemptions`` bound it).  ``error`` is set instead of
    raising when the request can never fit the engine's cache (or a
    policy's admission test rejects it)."""
    id: int
    prompt: List[int]
    max_new_tokens: int = 16
    qos: str = "standard"
    out_tokens: List[int] = field(default_factory=list)
    t_submit: Optional[int] = None
    t_admit: Optional[int] = None
    t_first: Optional[int] = None
    t_done: Optional[int] = None
    n_preempted: int = 0
    error: Optional[str] = None

    @property
    def done(self) -> bool:
        return len(self.out_tokens) >= self.max_new_tokens


class _EngineBase:
    """Queue + step-clock machinery shared by the slot and paged
    engines: submission/rejection bookkeeping, macro-step sizing, and
    the run loop.  Subclasses own admission and the request store
    (dense slots or paged rows) and implement ``step`` / ``_idle`` /
    ``_in_flight``."""

    MAX_STEPS = 512

    def __init__(self, cfg, *, prefill_chunk: int, decode_steps: int = 1,
                 policy=None, speculative=None):
        self.cfg = cfg
        self.prefill_chunk = max(1, prefill_chunk)
        self.decode_k = max(1, decode_steps)  # macro-step K
        # pluggable scheduling discipline (serving/scheduler.py);
        # default FIFO reproduces the historical admit/preempt order
        # bit-for-bit (tests/golden_decode.json)
        self.policy = make_policy(policy)
        # draft-verify speculative decoding (serving/speculative.py):
        # auto-gated off on archs whose cache cannot positionally roll
        # back (SSM/SWA/cross/MoE), exactly like prefix sharing
        self.spec = SpecConfig.make(speculative)
        self.spec_gated_off = (self.spec is not None
                               and not spec_supported(cfg))
        if self.spec_gated_off:
            self.spec = None
        self.spec_rounds = 0     # verify rounds run
        self.spec_drafted = 0    # draft tokens proposed (live rows)
        self.spec_accepted = 0   # draft tokens emitted as matches
        self.spec_emitted = 0    # tokens emitted by verify rounds
        self._spec_row_rounds = 0  # live (row, round) pairs
        self.queue: List[Request] = []
        self.rejected: List[Request] = []
        self.unfinished: List[Request] = []  # in flight at last run() exit
        self.tokens_generated = 0
        self.t = 0  # step counter (the engine clock for Request.t_*)
        # jitted callables, keyed by name, always invoked through this
        # dict (late binding lets serving/instrument.py count dispatches)
        self._jits = {}
        self.n_host_syncs = 0      # device->host materializations (decode)
        self.max_macro_tokens = 0  # most tokens emitted by one macro-step
        self.prefill_tokens = 0    # tokens actually prefilled (a prefix
        #                            hit shrinks this: the engine's
        #                            admission-cost / t_first budget)
        # spans and request marks (serving/instrument.py); records only
        self.trace = NULL_TRACE

    def submit(self, req: Request):
        if req.t_submit is None:  # resubmission keeps the original stamp
            req.t_submit = self.t
        self.queue.append(req)
        self.policy.on_submit(req, self.t)
        self.trace.mark(req.id, "submit")

    def _reject(self, req: Request, msg: str):
        """Fail one request without killing the engine (an oversized
        request used to trip a bare ``assert`` — stripped under
        ``python -O``, and fatal to every co-batched request)."""
        req.error = msg
        req.t_done = self.t
        self.rejected.append(req)

    def _prefill_chunks(self, req: Request, row: int, toks: List[int],
                        pos0: int = 0):
        """Chunked prefill of one admitted request through the
        ``_prefill_row`` hook.  ``pos0`` offsets the absolute positions
        — a prefix-cache hit prefills only the tail beyond the shared
        span (the skipped span never costs a prefill dispatch).  Each
        chunk's ``engine.prefill_prep`` span holds the chunk's input
        building as its own time and the ``prefill`` program's dispatch
        as its child."""
        tr = self.trace
        i = 0
        for c in chunk_sizes(len(toks), self.prefill_chunk):
            with tr.span("engine.prefill_prep"):
                self._prefill_row(row, np.asarray(toks[i:i + c],
                                                  dtype=np.int32), pos0 + i)
            i += c
        self.prefill_tokens += len(toks)
        tr.mark(req.id, "prefilled")

    def _next_tokens(self, width: int, active: List[int],
                     store: List[Optional[Request]]) -> np.ndarray:
        """Next decode input per active request: last prompt token
        before any generation, else its latest output token."""
        tokens = np.zeros((width, 1), dtype=np.int32)
        for i in active:
            req = store[i]
            tokens[i, 0] = (req.prompt[-1] if not req.out_tokens
                            else req.out_tokens[-1])
        return tokens

    def _k_eff(self, kmax: int) -> int:
        """Scan length for this macro-step: the smallest power of two
        >= the largest row budget, capped at ``decode_k`` — so at most
        log2(K) distinct scan programs ever compile (plus the raw
        ``decode_k`` program when K is not itself a power of two)."""
        k = 1
        while k < kmax and k * 2 <= self.decode_k:
            k *= 2
        return k if k >= kmax else self.decode_k

    def _macro_tail(self, store, budgets: np.ndarray, active: List[int],
                    max_len: int, t0: int,
                    k_cap: Optional[int] = None) -> List[tuple]:
        """Run one fused macro-step and do the host-side bookkeeping:
        slice each row's valid token prefix (its budget), bump ``pos``,
        stamp finishers at the device step they actually completed.
        Returns finished ``(row, request)`` pairs (the request still
        holds its row — the caller frees slots/blocks).

        ``k_cap`` bounds the scan length (paged engines: the smallest
        *block-clipped* budget).  A row masked mid-scan keeps running
        the decode compute, which advances its SSM recurrent state —
        harmless for a row that is *finished* (reset before reuse), but
        fatal for one that must resume, since stale SSM state, unlike
        stale KV, is never position-masked.  Capping the scan so only
        finished rows ever mask keeps resume state exact."""
        k_eff = self._k_eff(int(budgets.max()))
        if k_cap is not None and k_eff > k_cap:
            k_eff = 1 << (k_cap.bit_length() - 1)  # largest pow2 <= cap
            budgets = np.minimum(budgets, k_eff)
        tr = self.trace
        with tr.span("engine.decode_prep"):
            tokens = self._next_tokens(len(store), active, store)
            # pos is snapshotted before handing to jax: jnp.asarray
            # aliases numpy buffers on CPU and the jitted scan dispatches
            # asynchronously, so the += below must not race it
            out = self._forward_steps(tokens, self.pos.copy(), budgets,
                                      k_eff)
        with tr.span("engine.sync"):
            # reprolint: disable-next=host-sync -- the ONE deliberate sync
            # per macro-step (counted in n_host_syncs; <= 1/K per token)
            out = np.asarray(out)
        self.n_host_syncs += 1
        self.max_macro_tokens = max(self.max_macro_tokens,
                                    int(budgets.sum()))
        finished = []
        with tr.span("engine.deliver"):
            for i in active:
                req = store[i]
                v = int(budgets[i])
                if v > 0 and req.t_first is None and not req.out_tokens:
                    req.t_first = t0 + 1  # first token on device step 1
                    tr.mark(req.id, "first")
                req.out_tokens += [int(t) for t in out[i, :v]]
                self.tokens_generated += v
                self.pos[i] += v
                if req.done or self.pos[i] >= max_len - 1:
                    req.t_done = t0 + v
                    finished.append((i, req))
                    self.policy.on_done(req, t0 + v)
        self.t = t0 + k_eff
        return finished

    def _decode_jit(self, k: int):
        """Lazily-compiled fused macro-step program for scan length
        ``k`` (monolithic engines — requires ``self.model``; the
        pipelined engines build their stage-chained equivalent in
        ``_NetShimMixin._macro_jit``)."""
        key = f"decode{k}"
        if key not in self._jits:
            model = self.model

            # a named function, so the compiled module is called
            # ``jit_decode_steps`` in profiles (a partial has no name)
            def decode_steps(params, caches, batch, paged=None):
                return model.decode_steps(params, caches, batch, paged, k=k)

            self._jits[key] = jax.jit(decode_steps, donate_argnums=(1,))
        return self._jits[key]

    # ------------------------------------------------------------------
    # draft-verify speculative decoding (SERVING.md §Speculative
    # decoding; serving/speculative.py)
    # ------------------------------------------------------------------
    @property
    def acceptance_rate(self) -> float:
        """Fraction of proposed draft tokens emitted as exact matches."""
        return self.spec_accepted / max(1, self.spec_drafted)

    def spec_accept_mean(self) -> float:
        """Expected tokens emitted per live row per verify round (the
        accepted length + 1 correction/bonus) — what EC admission sees
        as the speculative service speedup (CapacityView.spec_accept)."""
        if self._spec_row_rounds == 0:
            return 1.0
        return self.spec_emitted / self._spec_row_rounds

    def _verify_jit(self, s: int):
        """Lazily-compiled fused verify program for chunk width ``s`` =
        K+1 (monolithic engines — requires ``self.model``; the
        pipelined engines chain their stages in
        ``_NetShimMixin._verify_chain_jit``)."""
        key = f"verify{s}"
        if key not in self._jits:
            self._jits[key] = jax.jit(self.model.verify_steps,
                                      donate_argnums=(1,))
        return self._jits[key]

    def _spec_tail(self, store, budgets: np.ndarray, active: List[int],
                   max_len: int, t0: int) -> List[tuple]:
        """Run one draft-verify round and do the host-side bookkeeping
        (the speculative analogue of :meth:`_macro_tail`).

        Each live row proposes K draft tokens (``spec.provider``), the
        target scores all of them in one fused chunk dispatch
        (``_forward_verify``), and the row advances by its accepted
        length + 1 (correction/bonus), clamped to its budget.  Rollback
        of rejected tails is purely positional: ``self.pos`` advances
        only past emitted tokens, the paged ledger keeps its blocks
        (stale KV above ``pos`` is position-masked and overwritten
        before any future read), and no KV is rewritten.  One round ==
        one engine clock step, so ``t_first``/TPOT stamps reflect the
        speculative speedup; host syncs stay at one per round (between
        1 and 1/(K+1) per emitted token).
        """
        K = self.spec.k
        width = len(store)
        tr = self.trace
        with tr.span("engine.decode_prep"):
            tokens = np.zeros((width, K + 1), dtype=np.int32)
            tokens[:, :1] = self._next_tokens(width, active, store)
            for i in active:
                req = store[i]
                tokens[i, 1:] = self.spec.provider.propose(
                    i, req.prompt + req.out_tokens, K)
                self.spec_drafted += K
            out = self._forward_verify(tokens, self.pos.copy(), budgets)
        with tr.span("engine.sync"):
            # reprolint: disable-next=host-sync -- the ONE deliberate sync
            # per verify round (counted in n_host_syncs; <= 1 per token)
            out = np.asarray(out)
        self.n_host_syncs += 1
        self.max_macro_tokens = max(self.max_macro_tokens,
                                    int(budgets.sum()))
        self.spec_rounds += 1
        finished = []
        with tr.span("engine.deliver"):
            for i in active:
                req = store[i]
                row = out[i]
                v = int((row >= 0).sum())  # accepted length + 1 <= budget
                if v > 0 and req.t_first is None and not req.out_tokens:
                    req.t_first = t0 + 1  # the round is one device step
                    tr.mark(req.id, "first")
                emitted = [int(t) for t in row[:v]]
                # matched drafts ARE the emitted tokens; the correction
                # token (if emitted) differs from its draft by construction
                self.spec_accepted += sum(
                    1 for j in range(min(v, K))
                    if emitted[j] == int(tokens[i, 1 + j]))
                self.spec_emitted += v
                self._spec_row_rounds += 1
                req.out_tokens += emitted
                self.tokens_generated += v
                self.pos[i] += v
                if req.done or self.pos[i] >= max_len - 1:
                    req.t_done = t0 + 1
                    finished.append((i, req))
                    self.policy.on_done(req, t0 + 1)
        self.t = t0 + 1
        return finished

    def _forward_verify(self, tokens: np.ndarray, pos: np.ndarray,
                        budgets: np.ndarray):
        """One fused draft-verify round over the (rows, K+1) chunk
        ``[next input, K drafts]``.  Returns (rows, K+1) int32 emitted
        tokens, -1 in non-emitted slots, still on the device: the
        caller's one sync per round brings them to the host."""
        raise NotImplementedError  # pragma: no cover - interface

    def step(self, k_cap: Optional[int] = None) -> List[Request]:
        raise NotImplementedError  # pragma: no cover - interface

    def _idle(self) -> bool:  # pragma: no cover - interface
        raise NotImplementedError

    def _in_flight(self) -> List[Request]:  # pragma: no cover - interface
        raise NotImplementedError

    def run(self, max_steps: Optional[int] = None) -> List[Request]:
        """Drive the engine until drained or ``max_steps`` decode steps
        have executed (macro-steps are clamped to the remaining budget,
        so K > 1 never overshoots it).  Requests still in flight (or
        queued) when the step budget runs out are surfaced in
        :attr:`unfinished` — they keep ``t_done is None`` and still
        hold their rows/blocks, so a further ``run()`` resumes them
        (they are *not* silently dropped)."""
        max_steps = self.MAX_STEPS if max_steps is None else max_steps
        done = []
        t_end = self.t + max_steps
        while self.t < t_end:
            done += self.step(k_cap=t_end - self.t)
            if not self.queue and self._idle():
                break
        self.unfinished = self._in_flight() + list(self.queue)
        return done

    # ------------------------------------------------------------------
    def _reset_row(self, row: int):  # pragma: no cover - interface
        raise NotImplementedError

    def _prefill_row(self, row: int, toks: np.ndarray, pos0: int):
        raise NotImplementedError  # pragma: no cover - interface

    def _forward_steps(self, tokens: np.ndarray, pos: np.ndarray,
                       budgets: np.ndarray, k: int):
        """One fused macro-step of ``k`` device decode iterations.
        Returns (rows, k) int32 token ids (row r valid to budgets[r]),
        still on the device: :meth:`_macro_tail`'s one sync per
        macro-step brings them to the host."""
        raise NotImplementedError  # pragma: no cover - interface


class _SlotEngine(_EngineBase):
    """Slot state machine: admission (chunked prefill), fused macro-step
    greedy decode, finish bookkeeping.  Forward passes are delegated to
    the subclass hooks:

    * ``_reset_row(slot)`` — clear one cache row before reuse;
    * ``_prefill_row(slot, toks, pos0)`` — process a prompt chunk
      (1, C) at absolute positions pos0.. for one slot;
    * ``_forward_steps(tokens, pos, budgets, k)`` — one fused macro-step
      of k decode iterations for the whole batch, returning (B, k) int32
      token ids (logits never leave the device).
    """

    def __init__(self, cfg, *, max_batch: int, cache_len: int,
                 prefill_chunk: int, decode_steps: int = 1, policy=None,
                 speculative=None):
        super().__init__(cfg, prefill_chunk=prefill_chunk,
                         decode_steps=decode_steps, policy=policy,
                         speculative=speculative)
        self.max_batch = max_batch
        self.cache_len = cache_len
        self.pos = np.zeros(max_batch, dtype=np.int32)
        self.slots: List[Optional[Request]] = [None] * max_batch

    def _free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def _idle(self) -> bool:
        return all(s is None for s in self.slots)

    def _in_flight(self) -> List[Request]:
        return [s for s in self.slots if s is not None]

    def _capacity_view(self, free_slots: int) -> CapacityView:
        """Dense capacity in policy units: one slot = one full
        ``cache_len`` granule (slot admission IS paging with one huge
        block)."""
        return CapacityView(free_tokens=free_slots * self.cache_len,
                            total_tokens=self.max_batch * self.cache_len,
                            granule=self.cache_len,
                            spec_accept=self.spec_accept_mean())

    def _admit(self):
        """Prefill queued requests into free slots: ``prefill_chunk``
        prompt tokens per jitted call (the final prompt token is fed as
        the first decode input in :meth:`step`).  The policy chooses
        *which* queued request is tried next and may reject it up
        front; a deferred choice blocks admission (head-of-line — it is
        never overtaken)."""
        free = self._free_slots()
        while free and self.queue:
            req = self.policy.next_admission(self.queue, self.t)
            if req is None:
                break
            # admission must leave max_new_tokens of cache headroom: the
            # decode loop stops a slot at pos >= cache_len - 1, so a
            # prompt of exactly cache_len would otherwise finish after a
            # SINGLE decode step, silently truncating the request
            if len(req.prompt) + req.max_new_tokens > self.cache_len:
                self.queue.remove(req)
                self._reject(
                    req, f"prompt of {len(req.prompt)} + max_new_tokens "
                         f"{req.max_new_tokens} exceeds cache_len "
                         f"{self.cache_len}")
                continue
            verdict, msg = self.policy.admission_test(
                req, self.t, self._capacity_view(len(free)))
            if verdict == REJECT:
                self.queue.remove(req)
                self._reject(req, msg or "rejected by admission test")
                continue
            if verdict == DEFER:
                break
            slot = free.pop(0)
            self.queue.remove(req)
            if req.t_admit is None:
                req.t_admit = self.t
            self.trace.mark(req.id, "admit")
            self.slots[slot] = req
            self._reset_row(slot)
            toks = req.prompt[:-1]
            self._prefill_chunks(req, slot, toks)
            self.pos[slot] = len(toks)

    # ------------------------------------------------------------------
    def step(self, k_cap: Optional[int] = None) -> List[Request]:
        """One engine iteration: admit + one fused macro-step of up to
        ``decode_k`` batched decode iterations (``k_cap`` further bounds
        the device steps — the run loop's remaining budget).  Returns
        finished requests."""
        tr = self.trace
        with tr.span("engine.step"):
            t0 = self.t
            self.t += 1  # admission/rejection stamps land on the 1st step
            self.policy.on_step(self.t, self.queue, self._in_flight())
            with tr.span("engine.admit"):
                self._admit()
            active = [i for i, s in enumerate(self.slots) if s is not None]
            if not active:
                return []
            # a speculative round emits up to K+1 tokens per row in ONE
            # device step, so its budget is token-denominated (drafts +
            # the correction/bonus token), not scan-step-denominated
            k = (self.spec.k + 1 if self.spec is not None
                 else self.decode_k if k_cap is None
                 else max(1, min(self.decode_k, k_cap)))
            # per-row step budget: never decode past max_new_tokens or
            # the cache-headroom stop (pos >= cache_len - 1) in the scan
            budgets = np.zeros(self.max_batch, dtype=np.int32)
            for i in active:
                req = self.slots[i]
                budgets[i] = max(1, min(
                    k, req.max_new_tokens - len(req.out_tokens),
                    self.cache_len - 1 - int(self.pos[i])))
            if self.spec is not None:
                finished = self._spec_tail(self.slots, budgets, active,
                                           self.cache_len, t0)
            else:
                finished = self._macro_tail(self.slots, budgets, active,
                                            self.cache_len, t0, k_cap=k_cap)
            done = []
            with tr.span("engine.release"):
                for i, req in finished:
                    self.slots[i] = None
                    self.policy.on_free(1, self.t)  # one slot granule back
                    done.append(req)
            return done


class _PagedEngine(_EngineBase):
    """Continuous-batching scheduler over a paged KV cache.

    The serving-side analogue of the paper's light-service online
    controller (SERVING.md maps the correspondence): instead of
    admitting work only when a whole dense slot frees, every scheduler
    step greedily admits queued requests while the block pool has
    room (token-level admission), grows running requests block-by-
    block, and resolves pool exhaustion by preempting the most
    recently admitted request (recompute on re-admission keeps greedy
    outputs token-identical).

    Decode rows (``max_rows``) bound *batch width* only; memory is
    bounded by the block pool, so with mixed-length requests the same
    cache memory sustains far more concurrent sequences than the dense
    engines (benchmarks/paged_bench.py measures this).

    Subclasses supply ``_reset_row`` / ``_prefill_row`` / ``_forward``
    (same contract as :class:`_SlotEngine`, with rows instead of
    slots).
    """

    MAX_STEPS = 4096  # preemption churn can stretch a busy run

    def __init__(self, cfg, *, max_rows: int, max_len: int,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 prefill_chunk: int = 16, watermark_blocks: int = 0,
                 decode_steps: int = 1, policy=None,
                 prefix_sharing: bool = True, speculative=None):
        super().__init__(cfg, prefill_chunk=prefill_chunk,
                         decode_steps=decode_steps, policy=policy,
                         speculative=speculative)
        self.max_rows = max_rows
        self.max_len = max_len
        # prefix sharing defaults on: with no overlapping full-block
        # prefixes in flight it is a no-op (token streams are pinned
        # identical either way — tests/test_paged.py's ON-vs-OFF sweep),
        # and the ledger auto-gates it off on SWA/SSM/cross archs
        self.pc = PagedCache(cfg, max_rows=max_rows, max_len=max_len,
                             block_size=block_size, num_blocks=num_blocks,
                             watermark_blocks=watermark_blocks,
                             share_prefixes=prefix_sharing)
        self.pos = np.zeros(max_rows, dtype=np.int32)
        self.rows: List[Optional[Request]] = [None] * max_rows
        self._admit_order: List[int] = []   # rows, oldest admission first
        self.n_preemptions = 0

    def _free_rows(self) -> List[int]:
        return [i for i, r in enumerate(self.rows) if r is None]

    def _idle(self) -> bool:
        return all(r is None for r in self.rows)

    def _in_flight(self) -> List[Request]:
        return [r for r in self.rows if r is not None]

    def _capacity_view(self) -> CapacityView:
        """Block-pool capacity in policy units (the watermark reserve is
        the ledger's own business — ``can_admit`` still arbitrates the
        final allocation)."""
        bs = self.pc.block_size
        return CapacityView(free_tokens=self.pc.free_blocks * bs,
                            total_tokens=self.pc.num_blocks * bs,
                            granule=bs,
                            shared_blocks=self.pc.probe_hit,
                            spec_accept=self.spec_accept_mean())

    def _admit(self):
        """Token-level admission: the policy's choice admits whenever a
        decode row is free and the pool holds its blocks (prompt +
        already-decoded prefix after a preemption).  Head-of-line order
        is kept — a blocked or deferred choice waits rather than being
        overtaken, so admission order (and with it preemption
        priority) is deterministic.  A policy admission test may
        instead *reject* the choice up front (effective-capacity test:
        the pool cannot free its deficit within the class's TTFT slack
        — ``_reject`` path, class-specific error)."""
        free = self._free_rows()
        while free and self.queue:
            req = self.policy.next_admission(self.queue, self.t)
            if req is None:
                break
            if (len(req.prompt) + req.max_new_tokens > self.max_len
                    or not self.pc.fits(
                        len(req.prompt) + req.max_new_tokens)):
                self.queue.remove(req)
                self._reject(
                    req, f"prompt of {len(req.prompt)} + max_new_tokens "
                         f"{req.max_new_tokens} exceeds capacity "
                         f"(max_len {self.max_len}, "
                         f"{self.pc.num_blocks} blocks)")
                continue
            verdict, msg = self.policy.admission_test(
                req, self.t, self._capacity_view())
            if verdict == REJECT:
                self.queue.remove(req)
                self._reject(req, msg or "rejected by admission test")
                continue
            total = len(req.prompt) + len(req.out_tokens)
            toks = (req.prompt + req.out_tokens)[:-1]
            wm = (None if any(r is not None for r in self.rows) else 0)
            if verdict == DEFER or not self.pc.can_admit(total,
                                                         watermark=wm,
                                                         tokens=toks):
                break
            self.queue.remove(req)
            row = free.pop(0)
            if not self.pc.admit(row, total, watermark=wm, tokens=toks):
                # can_admit above said yes; a refusal here is a ledger
                # bug and must not be silently skipped (nor live in an
                # assert — ``python -O`` would strip the allocation)
                raise RuntimeError(
                    f"ledger refused admission it just approved "
                    f"(row {row}, {total} tokens)")
            if req.t_admit is None:
                req.t_admit = self.t
            self.trace.mark(req.id, "admit")
            self.rows[row] = req
            self._admit_order.append(row)
            self._reset_row(row)
            # a prefix hit maps the matched span's blocks into the
            # table already filled — prefill only the tail beyond it
            # (hit == len(toks) skips prefill entirely: decode input is
            # the last token itself, not a prefill output)
            hit = self.pc.hit_tokens(row)
            self._prefill_chunks(req, row, toks[hit:], pos0=hit)
            self.pos[row] = len(toks)

    def _preempt(self, row: int):
        """Preempt-by-recompute: free the row's blocks and put the
        request back at the head of the queue carrying its generated
        prefix; re-admission re-prefills prompt+prefix, and greedy
        decode continues token-identically.  A request bounced
        ``policy.max_preemptions`` times is *evicted* to
        ``engine.rejected`` instead of requeued — bounding recompute
        churn (and the ``n_preempted`` property invariant,
        tests/test_scheduler_props.py)."""
        req = self.rows[row]
        self.pc.release(row)
        self.rows[row] = None
        self._admit_order.remove(row)
        self.n_preemptions += 1
        req.n_preempted += 1
        cap = self.policy.max_preemptions
        if cap is not None and req.n_preempted >= cap:
            self._reject(
                req, f"{req.qos}: evicted after {req.n_preempted} "
                     f"preemptions (max_preemptions={cap})")
            return
        self.queue.insert(0, req)
        self.policy.on_preempt(req, self.t)

    def _grow(self, k: int) -> tuple:
        """Block-budgeted macro-step sizing.  For every active row (in
        admission order): guarantee the block its *next* decode token
        writes into, preempting newest-admitted rows on pool exhaustion
        exactly as the per-token scheduler did (oldest rows are served
        first, so the oldest request always makes progress); then grow
        opportunistically — without preempting anyone — up to ``k``
        steps of coverage.  Returns ``(budgets, clip)``: the per-row
        step budgets (a row's in-scan writes [pos, pos + budget) are
        fully covered by blocks it owns, so the scan never needs the
        ledger) and the smallest *block-clipped* budget (None if no row
        was clipped) — the macro-step must not run longer than that,
        because a clipped row has to resume and a masked scan step
        would advance its SSM state (see :meth:`_macro_tail`)."""
        budgets = np.zeros(self.max_rows, dtype=np.int32)
        clip: Optional[int] = None
        for row in list(self._admit_order):
            req = self.rows[row]
            if req is None:
                continue
            pos = int(self.pos[row])
            while not self.pc.ensure(row, pos):
                # victim choice is the policy's (FIFO: newest admission,
                # the historical LIFO; EDF: most slack, TTFT-protected
                # rows exempt).  ``None`` — every candidate protected —
                # falls back to the needy row preempting itself, the
                # same terminating self-preempt the LIFO rule had when
                # the needy row was the newest.
                cands = [(r, self.rows[r]) for r in self._admit_order
                         if self.rows[r] is not None]
                victim = self.policy.select_victim(cands, self.t,
                                                   needy=row)
                if victim is None:
                    victim = row
                self._preempt(victim)
                if victim == row:
                    break
            if self.rows[row] is None:  # preempted itself
                continue
            want = max(1, min(k, req.max_new_tokens - len(req.out_tokens),
                              self.max_len - 1 - pos))
            steps = 1
            while steps < want and self.pc.ensure(row, pos + steps):
                steps += 1
            if steps < want:  # pool-limited: this row must resume
                clip = steps if clip is None else min(clip, steps)
            budgets[row] = steps
        return budgets, clip

    # ------------------------------------------------------------------
    def step(self, k_cap: Optional[int] = None) -> List[Request]:
        """One scheduler iteration: admit + grow/preempt + one fused
        macro-step of up to ``decode_k`` decode iterations (``k_cap``
        further bounds the device steps — the run loop's remaining
        budget).  Returns finished requests."""
        tr = self.trace
        with tr.span("engine.step"):
            t0 = self.t
            self.t += 1  # admission/rejection stamps land on the 1st step
            self.policy.on_step(self.t, self.queue, self._in_flight())
            with tr.span("engine.admit"):
                self._admit()
            # a speculative round's budget is token-denominated (see
            # _SlotEngine.step): _grow covers up to K+1 writes per row
            k = (self.spec.k + 1 if self.spec is not None
                 else self.decode_k if k_cap is None
                 else max(1, min(self.decode_k, k_cap)))
            with tr.span("engine.grow"):
                budgets, clip = self._grow(k)
            # any copy-on-write the ledger queued (a row about to write
            # a still-shared block) must hit the device pools before the
            # scan reads or writes the fresh copies
            with tr.span("engine.cow"):
                pairs = self.pc.take_pending_copies()
                if pairs:
                    self._apply_cow(pairs)
            active = [i for i, r in enumerate(self.rows) if r is not None]
            if not active:
                return []
            if self.spec is not None:
                # clip needs no special handling: emission clamps to the
                # block-covered budget, the verify chunk's writes beyond
                # it land in the scratch block (never read below the
                # accepted length), and the SSM-resume hazard clip
                # guards against cannot occur — speculation is gated to
                # pure-attention archs
                finished = self._spec_tail(self.rows, budgets, active,
                                           self.max_len, t0)
            else:
                caps = [c for c in (clip, k_cap) if c is not None]
                cap = min(caps) if caps else None
                finished = self._macro_tail(self.rows, budgets, active,
                                            self.max_len, t0, k_cap=cap)
            done = []
            with tr.span("engine.release"):
                for i, req in finished:
                    self.rows[i] = None
                    self._admit_order.remove(i)
                    fb0 = self.pc.free_blocks
                    self.pc.release(i)
                    # completion releases feed the EC policy's service
                    # model (preemption frees are churn, not service)
                    self.policy.on_free(self.pc.free_blocks - fb0, self.t)
                    done.append(req)
            return done

    def _apply_cow(self, pairs: List[tuple]):
        """Apply queued COW pool copies ``[(src, dst), ...]`` to the
        device-side caches.  The base implementation is a host no-op —
        enough for ledgers without device pools (``FakeEngine``'s
        integer recurrence keeps no per-position state); real engines
        override with a jitted :func:`paged_copy_blocks`."""

    @property
    def active_rows(self) -> int:
        return sum(1 for r in self.rows if r is not None)


class ServingEngine(_SlotEngine):
    """Monolithic engine: one jitted macro-step/prefill over the full
    model.  All cache-carrying jits donate the cache argument (the
    engine rebinds ``self.caches`` to the output every call)."""

    def __init__(self, cfg, params=None, *, max_batch: int = 4,
                 cache_len: int = 128, seed: int = 0,
                 prefill_chunk: int = 16, decode_steps: int = 1,
                 policy=None, speculative=None, quantization=None):
        super().__init__(cfg, max_batch=max_batch, cache_len=cache_len,
                         prefill_chunk=prefill_chunk,
                         decode_steps=decode_steps, policy=policy,
                         speculative=speculative)
        self.model = build_model(cfg, qformat=quantization)
        self.quantization = self.model.qformat
        key = jax.random.PRNGKey(seed)
        self.params = params if params is not None else self.model.init(key)
        # pack projection weights once at construction; the packed
        # leaves enter every jit as static-shaped non-donated operands
        # (weights are not linear state — only caches donate), so no
        # recompile churn and the donation contract is untouched
        self.params = quantize_params(self.params, self.quantization)
        self.caches = self.model.init_cache(max_batch, cache_len)
        self._jits["prefill"] = jax.jit(self.model.prefill_chunk,
                                        donate_argnums=(1,))
        self._jits["reset"] = jax.jit(reset_cache_row, donate_argnums=(0,))

    def _reset_row(self, slot: int):
        self.caches = self._jits["reset"](self.caches, jnp.int32(slot))

    def _prefill_row(self, slot: int, toks: np.ndarray, pos0: int):
        _, self.caches = self._jits["prefill"](
            self.params, self.caches, jnp.asarray(toks[None]),
            jnp.int32(pos0), jnp.int32(slot))

    def _forward_steps(self, tokens: np.ndarray, pos: np.ndarray,
                       budgets: np.ndarray, k: int):
        toks, self.caches = self._decode_jit(k)(
            self.params, self.caches,
            {"token": jnp.asarray(tokens), "pos": jnp.asarray(pos),
             "budget": jnp.asarray(budgets)})
        return toks

    def _forward_verify(self, tokens: np.ndarray, pos: np.ndarray,
                        budgets: np.ndarray):
        emit, self.caches = self._verify_jit(tokens.shape[1])(
            self.params, self.caches,
            {"token": jnp.asarray(tokens), "pos": jnp.asarray(pos),
             "budget": jnp.asarray(budgets)})
        return emit


class PagedServingEngine(_PagedEngine):
    """Monolithic paged engine: the continuous scheduler over one
    jitted paged macro-step/prefill (``Model.decode_steps`` with block
    tables / ``paged_prefill_chunk``).  Greedy outputs are
    token-identical to :class:`ServingEngine` at equal
    ``max_len``/``cache_len`` for every ``decode_steps``
    (tests/test_paged.py).  Block tables ride device-side through
    ``PagedCache.meta``'s incremental snapshot — re-uploaded only when
    the ledger changed."""

    def __init__(self, cfg, params=None, *, max_rows: int = 8,
                 max_len: int = 128, block_size: int = 16,
                 num_blocks: Optional[int] = None, seed: int = 0,
                 prefill_chunk: int = 16, watermark_blocks: int = 0,
                 decode_steps: int = 1, policy=None,
                 prefix_sharing: bool = True, speculative=None,
                 quantization=None):
        super().__init__(cfg, max_rows=max_rows, max_len=max_len,
                         block_size=block_size, num_blocks=num_blocks,
                         prefill_chunk=prefill_chunk,
                         watermark_blocks=watermark_blocks,
                         decode_steps=decode_steps, policy=policy,
                         prefix_sharing=prefix_sharing,
                         speculative=speculative)
        self.model = build_model(cfg, qformat=quantization)
        self.quantization = self.model.qformat
        key = jax.random.PRNGKey(seed)
        self.params = params if params is not None else self.model.init(key)
        # packed at construction; static non-donated jit operands (see
        # ServingEngine — same contract, reprolint quant-static-weights)
        self.params = quantize_params(self.params, self.quantization)
        self.caches = self.pc.struct(self.model.dtype)
        self._jits["prefill"] = jax.jit(self.model.paged_prefill_chunk,
                                        donate_argnums=(1,))
        segs = self.model.segments
        self._jits["reset"] = jax.jit(
            lambda caches, row, xids: paged_reset_row(caches, segs, row,
                                                      xids),
            donate_argnums=(0,))
        has_swa = self.pc.has_swa
        self._jits["cow"] = jax.jit(
            lambda caches, src, dst: paged_copy_blocks(
                caches, segs, src, dst, has_swa=has_swa),
            donate_argnums=(0,))

    def _apply_cow(self, pairs):
        src = jnp.asarray([s for s, _ in pairs], jnp.int32)
        dst = jnp.asarray([d for _, d in pairs], jnp.int32)
        self.caches = self._jits["cow"](self.caches, src, dst)

    def _reset_row(self, row: int):
        xids = jnp.asarray(self.pc.cross_tables[row].copy())
        self.caches = self._jits["reset"](self.caches, jnp.int32(row), xids)

    def _prefill_row(self, row: int, toks: np.ndarray, pos0: int):
        _, self.caches = self._jits["prefill"](
            self.params, self.caches, jnp.asarray(toks[None]),
            jnp.int32(pos0), jnp.int32(row), self.pc.meta(row=row))

    def _forward_steps(self, tokens: np.ndarray, pos: np.ndarray,
                       budgets: np.ndarray, k: int):
        toks, self.caches = self._decode_jit(k)(
            self.params, self.caches,
            {"token": jnp.asarray(tokens), "pos": jnp.asarray(pos),
             "budget": jnp.asarray(budgets)},
            self.pc.meta())
        return toks

    def _forward_verify(self, tokens: np.ndarray, pos: np.ndarray,
                        budgets: np.ndarray):
        emit, self.caches = self._verify_jit(tokens.shape[1])(
            self.params, self.caches,
            {"token": jnp.asarray(tokens), "pos": jnp.asarray(pos),
             "budget": jnp.asarray(budgets)},
            self.pc.meta())
        return emit
