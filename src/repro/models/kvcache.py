"""Per-segment cache/state construction: dense slot rows and paged pools.

Two cache layouts share the same per-segment pytree structure (one list
entry per segment, leaves with a leading layer dim):

* **Dense** (:func:`cache_struct`) — one full ``seq_len`` row per batch
  slot: attn/swa leaves are ``(n_layers, batch, slots, kv_heads, hd)``
  where slot == absolute position for attn and ``pos % window`` for the
  SWA ring.  Memory is reserved worst-case per slot, so admission is
  slot-granular (`serving/engine.py`'s dense engines).
* **Paged** (:class:`PagedCache` + :meth:`PagedCache.struct`) —
  fixed-size blocks in a shared pool: attn/swa/cross leaves are
  ``(n_layers, num_physical_blocks, block_size, kv_heads * hd)`` (one
  row per slot, its heads side by side) and a request's logical slot
  ``s`` lives at ``(tables[row, s // block_size], s % block_size)``.
  Admission is block-granular (token-level), so mixed-length workloads
  share the pool (`serving/engine.py`'s paged engines).

Cache layout invariants (relied on across models/serving/kernels):

* physical block 0 of every paged pool is the **scratch block**: never
  allocated, it absorbs the writes of inactive decode rows; block-table
  entries of unallocated logical blocks point at scratch, and every
  read through them is masked by position;
* stale attn/swa KV needs no zeroing on block reuse — attention masks
  slots above ``pos`` (and the SWA ring is fully rewritten before its
  all-slots-valid regime at ``pos >= window - 1``);
* cross KV (``xk``/``xv``) is *not* position-masked, so a request's
  cross blocks are zeroed at admission (token requests carry no
  frontend; parity with the dense engines' zero-initialised cross
  rows);
* SSM/conv state stays per-request dense (``(n_layers, rows, ...)``)
  in both layouts and must be zeroed on row (re)use — stale KV is
  masked by position, stale recurrent state is not;
* attn-pool blocks may be **shared** between requests under
  copy-on-write prefix sharing (:class:`PagedCache` with
  ``share_prefixes``): a block's content is a pure function of the
  token-id prefix it caches, a per-block refcount tracks its owners,
  and any write to a block with refcount > 1 first copies it
  (SERVING.md §Prefix sharing);
* speculative write semantics: a draft-verify round writes KV for all
  K+1 chunk positions unconditionally, then the engine advances
  ``pos`` only past the accepted prefix — rejected positions become
  ordinary stale KV (masked by position, overwritten by the next
  chunk), which is why speculative rollback is a ledger-side position
  decrement with **no KV rewrite**, and why it is gated to
  pure-attention archs (stale SSM/recurrent state is not
  position-masked; SERVING.md §Speculative decoding).
"""
from __future__ import annotations

from collections import Counter
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.transformer import build_segments, segment_range


def cache_struct(cfg, batch: int, seq_len: int, dtype, layers=None) -> list:
    """One entry per segment, each a dict with leading layer dim.

    ``layers=(lo, hi)`` restricts the structure to that decoder layer
    range (a pipeline stage's slice — aligned with
    :func:`repro.models.transformer.segment_range`).
    """
    segs = (build_segments(cfg) if layers is None
            else segment_range(cfg, *layers))
    caches = []
    for seg in segs:
        n = seg.length
        if seg.kind in ("attn", "cross") or (
                seg.kind == "swa" and not cfg.window):
            s = seq_len
        elif seg.kind == "swa":
            s = min(cfg.window, seq_len)
        if seg.kind in ("attn", "swa"):
            c = {
                "k": jnp.zeros((n, batch, s, cfg.n_kv_heads, cfg.head_dim),
                               dtype),
                "v": jnp.zeros((n, batch, s, cfg.n_kv_heads, cfg.head_dim),
                               dtype),
            }
            if cfg.is_encoder_decoder:
                c["xk"] = jnp.zeros(
                    (n, batch, cfg.encoder_seq, cfg.n_kv_heads, cfg.head_dim),
                    dtype)
                c["xv"] = jnp.zeros_like(c["xk"])
        elif seg.kind == "cross":
            src = cfg.n_image_tokens or cfg.encoder_seq
            c = {
                "xk": jnp.zeros((n, batch, src, cfg.n_kv_heads, cfg.head_dim),
                                dtype),
                "xv": jnp.zeros((n, batch, src, cfg.n_kv_heads, cfg.head_dim),
                                dtype),
            }
        elif seg.kind == "mamba1":
            di, ds = cfg.d_inner_eff, cfg.ssm_state
            c = {
                "h": jnp.zeros((n, batch, di, ds), jnp.float32),
                "conv": jnp.zeros((n, batch, cfg.conv_width - 1, di), dtype),
            }
        elif seg.kind == "mamba2":
            di, ds = cfg.d_inner_eff, cfg.ssm_state
            nh = di // cfg.mamba2_headdim
            c = {
                "h": jnp.zeros((n, batch, nh, cfg.mamba2_headdim, ds),
                               jnp.float32),
                "conv": jnp.zeros((n, batch, cfg.conv_width - 1, di), dtype),
            }
        else:
            raise ValueError(seg.kind)
        caches.append(c)
    return caches


def cache_bytes(cfg, batch: int, seq_len: int, bytes_per_el: int = 2) -> int:
    struct = jax.eval_shape(lambda: cache_struct(cfg, batch, seq_len,
                                                 jnp.bfloat16))
    return sum(x.size * bytes_per_el for x in jax.tree.leaves(struct))


# ----------------------------------------------------------------------
# Paged cache: block pools + per-request block tables
# ----------------------------------------------------------------------
class PagedCache:
    """Host-side paged-cache ledger: free lists + per-request block tables.

    Three block groups cover the attention segment kinds (SSM state is
    per-request dense, see module docstring):

    ``attn``
        the shared contention pool — ``num_blocks`` usable blocks of
        ``block_size`` tokens; one block id covers the same logical
        token range in *every* attn-kind layer's pool.  Logical slot ==
        absolute position; a request holds
        ``ceil(tokens / block_size)`` blocks and grows block-by-block
        as it decodes (:meth:`ensure`).  This is the group token-level
        admission and preemption arbitrate over.
    ``swa``
        per-request ring of ``ceil(min(window, max_len) / bs)`` blocks
        holding ring slot ``pos % window``; sized worst-case
        (``max_rows`` full rings) so allocation never fails and the
        ring never contends with the attn pool.
    ``cross``
        per-request ``ceil(src / bs)`` blocks of encoder/frontend KV,
        allocated and zeroed at admission (cross reads are not
        position-masked).

    The ledger is pure numpy/python — deterministic LIFO free lists,
    no jax state.  Pool arrays are built separately by :meth:`struct`
    (optionally restricted to a pipeline stage's layer range) so one
    ledger can govern several stage-sliced pools that share block ids.

    ``watermark_blocks`` holds back free attn blocks at admission time:
    a new request is admitted only if its prompt fits *and* the pool
    stays above the watermark, reserving headroom for the decode growth
    of already-running requests (fewer preemptions at high load).

    **Prefix sharing** (``share_prefixes=True``, SERVING.md §Prefix
    sharing).  Attn blocks become *shared* resources under a per-block
    refcount: a host-side prefix index maps the token ids of every
    fully-prefilled block (keyed by the request's whole token prefix up
    to and including that block, so a match is exact by construction —
    attention KV at position ``p`` is a pure function of tokens
    ``[0, p]``) to the physical block caching it.  :meth:`admit` with
    ``tokens=`` matches the longest indexed full-block prefix and maps
    those blocks into the new request's table with a refcount bump
    instead of allocating + re-prefilling them; :meth:`release` (and
    preemption, which uses the same path) decrements refcounts, and a
    block returns to the free list only at refcount zero.  A write into
    a block with refcount > 1 (:meth:`ensure`) triggers
    **copy-on-write**: a fresh block replaces it in the writer's table
    and the pending device-side pool copy is queued in
    :attr:`pending_copies` for the engine to apply before its next
    forward.  Sharing is only sound when the attn pool is the *only*
    per-position state a prefix builds — SSM/conv state, the SWA ring,
    and cross KV are per-request and not content-addressed — so it
    auto-disables (:attr:`sharing_supported`) on configs with those
    segment kinds, and ``admit`` then behaves exactly as before.
    """

    def __init__(self, cfg, *, max_rows: int, max_len: int,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 watermark_blocks: int = 0, share_prefixes: bool = False):
        assert max_len % block_size == 0, (max_len, block_size)
        self.cfg = cfg
        self.max_rows = max_rows
        self.max_len = max_len
        self.block_size = block_size
        self.nb_logical = max_len // block_size
        self.watermark_blocks = watermark_blocks

        kinds = {s.kind for s in build_segments(cfg)}
        self.has_swa = "swa" in kinds and bool(cfg.window)
        self.window_eff = min(cfg.window, max_len) if self.has_swa else 0
        self.nb_swa = (-(-self.window_eff // block_size)
                       if self.has_swa else 0)
        src = (cfg.n_image_tokens or cfg.encoder_seq
               if ("cross" in kinds or cfg.is_encoder_decoder) else 0)
        self.cross_src = src
        self.nb_cross = -(-src // block_size) if src else 0

        self.num_blocks = (max_rows * self.nb_logical
                           if num_blocks is None else num_blocks)
        self._groups = {"attn": self.num_blocks,
                        "swa": max_rows * self.nb_swa,
                        "cross": max_rows * self.nb_cross}
        # prefix sharing: only the attn pool is content-addressed (SSM/
        # conv state, the SWA ring, and cross KV are per-request state a
        # skipped prefill would not rebuild)
        self.sharing_supported = not (
            self.has_swa or self.nb_cross
            or kinds & {"mamba1", "mamba2"})
        self.share_prefixes = bool(share_prefixes) and self.sharing_supported
        # per-attn-block owner count; a block is free iff refcount 0
        self._ref = np.zeros(self.num_blocks + 1, np.int32)
        # token-prefix bytes -> physical block caching that full block,
        # plus the reverse map for de-indexing at refcount zero
        self._prefix_index: Dict[bytes, int] = {}
        self._block_key: Dict[int, bytes] = {}
        # COW pool copies (src, dst) awaiting device application —
        # engines drain via take_pending_copies() before each forward
        self.pending_copies: List[Tuple[int, int]] = []
        self._hit_tokens_row = np.zeros(max_rows, np.int32)
        self.n_prefix_hits = 0      # admissions that matched >= 1 block
        self.prefix_tokens_hit = 0  # prefill tokens skipped, cumulative
        self.blocks_saved = 0       # allocations avoided by sharing
        self.n_cow_copies = 0
        # LIFO free lists; block id 0 is the scratch block of each group
        self._free = {g: list(range(n, 0, -1))
                      for g, n in self._groups.items()}
        self._held = {g: [[] for _ in range(max_rows)]
                      for g in self._groups}
        self.tables = np.zeros((max_rows, self.nb_logical), np.int32)
        self.swa_tables = np.zeros((max_rows, max(self.nb_swa, 1)), np.int32)
        self.cross_tables = np.zeros((max_rows, max(self.nb_cross, 1)),
                                     np.int32)
        # incremental device snapshot: the ledger version bumps on every
        # table mutation (admit/growth/release/preempt); meta() re-uploads
        # only when the version moved, so steady-state decode reuses one
        # immutable device copy instead of copying every table per forward
        self._version = 0
        self._meta_version = -1
        self._meta_cache: Optional[dict] = None
        self.n_meta_uploads = 0

    # -------------------------------------------------------------- pools
    def struct(self, dtype, layers=None) -> list:
        """Block-pool pytree for decoder layers ``layers`` (default all).

        Mirrors :func:`cache_struct` segment-for-segment; attn/swa/cross
        leaves swap the per-slot batch rows for
        ``(group_blocks + 1, block_size)`` physical pools (+1 for the
        scratch block) whose slots are rows of ``kv_heads * hd``, SSM
        leaves keep ``max_rows`` state rows.  A slot row keeps the TPU's
        tiles dense for the scatter that writes it and the block gather
        that reads it (128-lane tiles would pad a ``(5, 64)`` slot
        6.4x; SERVING.md §Donation).
        """
        cfg = self.cfg
        segs = (build_segments(cfg) if layers is None
                else segment_range(cfg, *layers))
        bs, kvh, hd = self.block_size, cfg.n_kv_heads, cfg.head_dim
        nb_attn = self._groups["attn"] + 1
        nb_swa = self._groups["swa"] + 1
        nb_cross = self._groups["cross"] + 1
        caches = []
        for seg in segs:
            n = seg.length
            if seg.kind in ("attn", "swa"):
                nb = (nb_swa if (seg.kind == "swa" and cfg.window)
                      else nb_attn)
                c = {"k": jnp.zeros((n, nb, bs, kvh * hd), dtype),
                     "v": jnp.zeros((n, nb, bs, kvh * hd), dtype)}
                if cfg.is_encoder_decoder:
                    c["xk"] = jnp.zeros((n, nb_cross, bs, kvh * hd), dtype)
                    c["xv"] = jnp.zeros_like(c["xk"])
            elif seg.kind == "cross":
                c = {"xk": jnp.zeros((n, nb_cross, bs, kvh * hd), dtype),
                     "xv": jnp.zeros((n, nb_cross, bs, kvh * hd), dtype)}
            elif seg.kind == "mamba1":
                di, ds = cfg.d_inner_eff, cfg.ssm_state
                c = {"h": jnp.zeros((n, self.max_rows, di, ds), jnp.float32),
                     "conv": jnp.zeros((n, self.max_rows, cfg.conv_width - 1,
                                        di), dtype)}
            elif seg.kind == "mamba2":
                di, ds = cfg.d_inner_eff, cfg.ssm_state
                nh = di // cfg.mamba2_headdim
                c = {"h": jnp.zeros((n, self.max_rows, nh,
                                     cfg.mamba2_headdim, ds), jnp.float32),
                     "conv": jnp.zeros((n, self.max_rows, cfg.conv_width - 1,
                                        di), dtype)}
            else:
                raise ValueError(seg.kind)
            caches.append(c)
        return caches

    # ---------------------------------------------------------- metadata
    def meta(self, row: Optional[int] = None) -> dict:
        """Block-table metadata for a jitted forward call.

        Snapshot copies (``jnp.asarray`` aliases numpy buffers on CPU
        and the jitted callee dispatches asynchronously — the ledger
        must stay mutable on the host side).  ``row`` restricts tables
        to one request (the chunked-prefill path).

        The full-table snapshot (``row=None``, the per-decode path) is
        cached against :attr:`_version`: it is rebuilt only when the
        ledger actually changed since the last upload — during steady-
        state decode the same immutable device arrays are handed to
        every macro-step.  (:attr:`n_meta_uploads` counts rebuilds;
        benchmarks/engine_bench.py reports uploads per token.)
        """
        if row is None:
            if self._meta_version == self._version:
                return self._meta_cache
            self._meta_cache = self._build_meta(slice(None))
            self._meta_version = self._version
            self.n_meta_uploads += 1
            return self._meta_cache
        return self._build_meta(slice(row, row + 1))

    def _build_meta(self, sel) -> dict:
        out = {"tables": jnp.asarray(self.tables[sel].copy())}
        if self.has_swa:
            out["swa_tables"] = jnp.asarray(self.swa_tables[sel].copy())
        if self.nb_cross:
            out["cross_tables"] = jnp.asarray(self.cross_tables[sel].copy())
        return out

    # -------------------------------------------------------- accounting
    def blocks_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)

    @property
    def free_blocks(self) -> int:
        return len(self._free["attn"])

    @property
    def used_blocks(self) -> int:
        return self.num_blocks - self.free_blocks

    def utilization(self) -> float:
        return (self.used_blocks / self.num_blocks) if self.num_blocks else 0.0

    def fits(self, total_tokens: int) -> bool:
        """Can a request ever run: worst-case footprint vs pool size."""
        return self.blocks_needed(total_tokens) <= self.num_blocks

    # ---------------------------------------------------- prefix index
    def _prefix_key(self, tokens, logical: int) -> bytes:
        """Index key of logical block ``logical`` for a request whose
        prefilled token ids are ``tokens``: the *whole* prefix through
        that block, so equal keys imply bitwise-equal cached KV."""
        end = (logical + 1) * self.block_size
        return np.asarray(tokens[:end], np.int32).tobytes()

    def _match_blocks(self, tokens) -> List[int]:
        """Longest indexed full-block prefix of ``tokens`` (the
        request's to-be-prefilled ids), as physical block ids.  Only
        blocks *fully covered* by ``tokens`` can match — the block
        holding a request's first decode write is never shared."""
        if not self.share_prefixes or tokens is None:
            return []
        out: List[int] = []
        for j in range(len(tokens) // self.block_size):
            blk = self._prefix_index.get(self._prefix_key(tokens, j))
            if blk is None:
                break
            out.append(blk)
        return out

    def probe_hit(self, tokens) -> int:
        """Blocks an admission with ``tokens`` would share rather than
        allocate — the scheduler's effective-capacity admission test
        subtracts this from the modeled block demand
        (`serving/scheduler.py::EDFCapacityPolicy`)."""
        return len(self._match_blocks(tokens))

    def hit_tokens(self, row: int) -> int:
        """Prefill tokens row ``row``'s last :meth:`admit` matched (a
        multiple of ``block_size``) — the span the engine skips."""
        return int(self._hit_tokens_row[row])

    def _register_prefixes(self, row: int, tokens) -> None:
        """Index every fully-prefilled block of ``tokens`` that is not
        indexed yet (matched blocks are already present under the same
        keys).  Called at admit time: the row's prefill writes the
        claimed content before any matcher can read it."""
        for j in range(len(tokens) // self.block_size):
            key = self._prefix_key(tokens, j)
            if key not in self._prefix_index:
                blk = int(self.tables[row, j])
                self._prefix_index[key] = blk
                self._block_key[blk] = key

    def _deindex(self, blk: int) -> None:
        key = self._block_key.pop(blk, None)
        if key is not None and self._prefix_index.get(key) == blk:
            del self._prefix_index[key]

    def can_admit(self, n_tokens: int, watermark: Optional[int] = None,
                  tokens=None) -> bool:
        """``watermark`` overrides the configured headroom — the
        scheduler drops it to 0 when nothing is running (headroom only
        exists to protect active requests' decode growth; holding an
        idle pool back would deadlock a lone large request).
        ``tokens`` (the to-be-prefilled ids) lets a prefix hit shrink
        the fresh-block demand."""
        wm = self.watermark_blocks if watermark is None else watermark
        need = self.blocks_needed(n_tokens) - len(self._match_blocks(tokens))
        return (len(self._free["attn"]) - wm >= need
                and len(self._free["swa"]) >= self.nb_swa
                and len(self._free["cross"]) >= self.nb_cross)

    def _alloc(self, group: str, row: int, table: np.ndarray,
               logical: int) -> bool:
        free = self._free[group]
        if not free:
            return False
        blk = free.pop()
        self._held[group][row].append(blk)
        table[row, logical] = blk
        if group == "attn":
            self._ref[blk] = 1
        self._version += 1
        return True

    def _alloc_or_die(self, group: str, row: int, table: np.ndarray,
                      logical: int):
        # callers hold the can_admit guarantee; a failure here is ledger
        # corruption, and must raise even under ``python -O``
        if not self._alloc(group, row, table, logical):
            raise RuntimeError(
                f"{group} pool exhausted mid-admit (row {row}, logical "
                f"{logical}) despite can_admit — ledger corrupted")

    def admit(self, row: int, n_tokens: int,
              watermark: Optional[int] = None, tokens=None) -> bool:
        """Allocate row ``row``'s blocks for logical slots [0, n_tokens)
        plus its full SWA ring and cross blocks.  All-or-nothing.

        With sharing enabled and ``tokens`` (the ids the engine is
        about to prefill, i.e. ``(prompt + out)[:-1]``), the longest
        indexed full-block prefix is *mapped* instead of allocated:
        matched blocks enter the row's table with a refcount bump, and
        :meth:`hit_tokens` reports the span whose prefill the engine
        skips.  Fresh fully-prefilled blocks are registered in the
        prefix index for later arrivals to match."""
        if any(self._held[g][row] for g in self._held):
            raise RuntimeError(f"admit: row {row} still holds blocks")
        matched = self._match_blocks(tokens)
        if not self.can_admit(n_tokens, watermark=watermark,
                              tokens=tokens):
            return False
        for j, blk in enumerate(matched):
            self._ref[blk] += 1
            self._held["attn"][row].append(blk)
            self.tables[row, j] = blk
        if matched:
            self._version += 1
        for j in range(len(matched), self.blocks_needed(n_tokens)):
            self._alloc_or_die("attn", row, self.tables, j)
        for j in range(self.nb_swa):
            self._alloc_or_die("swa", row, self.swa_tables, j)
        for j in range(self.nb_cross):
            self._alloc_or_die("cross", row, self.cross_tables, j)
        if self.share_prefixes and tokens is not None:
            self._register_prefixes(row, tokens)
        hit = len(matched) * self.block_size
        self._hit_tokens_row[row] = hit
        if matched:
            self.n_prefix_hits += 1
            self.prefix_tokens_hit += hit
            self.blocks_saved += len(matched)
        return True

    def _cow(self, row: int, logical: int, src: int) -> bool:
        """Copy-on-write: give ``row`` a private copy of shared block
        ``src`` before it writes into logical slot ``logical``.  The
        device-side pool copy is queued in :attr:`pending_copies`
        (engines apply it before their next forward); the ledger side —
        table entry, held list, refcounts — swaps immediately.  Returns
        False when no free block exists (the scheduler must preempt);
        the shared mapping is left untouched in that case."""
        free = self._free["attn"]
        if not free:
            return False
        dst = free.pop()
        self._ref[dst] = 1
        self._ref[src] -= 1
        held = self._held["attn"][row]
        held[held.index(src)] = dst
        self.tables[row, logical] = dst
        self.pending_copies.append((src, dst))
        self.n_cow_copies += 1
        self._version += 1
        return True

    def ensure(self, row: int, pos: int) -> bool:
        """Grow row ``row`` to cover a *write* at absolute position
        ``pos`` (decode step).  A covered position whose block is
        shared (refcount > 1) triggers copy-on-write; a covered block
        this row owns exclusively but that is still in the prefix index
        is de-indexed (its content is about to diverge from the indexed
        token prefix).  Returns False when the attn pool is exhausted —
        the scheduler must preempt."""
        logical = min(pos, self.max_len - 1) // self.block_size
        held = len(self._held["attn"][row])
        if logical < held:
            blk = int(self.tables[row, logical])
            if self._ref[blk] > 1:
                return self._cow(row, logical, blk)
            if blk in self._block_key:
                self._deindex(blk)
            return True
        if logical != held:  # growth is 1 block/step by construction
            raise RuntimeError(
                f"ensure: row {row} skipped to logical block {logical} "
                f"with only {held} held")
        return self._alloc("attn", row, self.tables, logical)

    def take_pending_copies(self) -> List[Tuple[int, int]]:
        """Drain the queued COW ``(src, dst)`` pool copies.  The caller
        must apply them to every attn-pool leaf (device side) before
        the next forward reads or writes the ``dst`` blocks."""
        out, self.pending_copies = self.pending_copies, []
        return out

    def release(self, row: int):
        """Drop every block reference row ``row`` holds (completion or
        preemption).  Attn blocks are refcounted: a block returns to
        the free list (and leaves the prefix index) only when its last
        owner releases it — a preempted request's shared prefix blocks
        stay resident for their surviving sharers."""
        blocks, free = self._held["attn"][row], self._free["attn"]
        for b in reversed(blocks):  # LIFO order matches the old ledger
            if self._ref[b] <= 0:  # guard must survive ``python -O``
                raise RuntimeError(f"double free of attn block {b}")
            self._ref[b] -= 1
            if self._ref[b] == 0:
                self._deindex(b)
                free.append(b)
        blocks.clear()
        for g, table in (("swa", self.swa_tables),
                         ("cross", self.cross_tables)):
            blocks, free = self._held[g][row], self._free[g]
            dup = set(blocks) & set(free)
            if dup:  # guard must survive ``python -O``
                raise RuntimeError(
                    f"double free of {g} blocks {sorted(dup)}")
            free.extend(reversed(blocks))
            blocks.clear()
        self.tables[row] = 0
        self.swa_tables[row] = 0
        self.cross_tables[row] = 0
        self._hit_tokens_row[row] = 0
        self._version += 1

    def check(self):
        """Ledger invariants: every block is exactly one of
        {free, scratch, referenced}; attn refcounts equal both the
        held-list multiplicity and the table occupancy (sharing maps a
        block into several rows' tables, once each); no leak, no
        double-book; index entries only on live attn blocks."""
        for g, n in self._groups.items():
            free = self._free[g]
            held = [b for row in self._held[g] for b in row]
            assert len(set(free)) == len(free), f"{g}: dup in free list"
            assert 0 not in free and 0 not in held, f"{g}: scratch booked"
            if g == "attn":
                held_n = Counter(held)
                occupancy = Counter(
                    b for row in range(self.max_rows)
                    for b in self.tables[row].tolist() if b != 0)
                free_set = set(free)
                for b in range(1, n + 1):
                    r = int(self._ref[b])
                    assert r == held_n.get(b, 0), \
                        f"attn: block {b} refcount {r} != held {held_n.get(b, 0)}"
                    assert r == occupancy.get(b, 0), \
                        (f"attn: block {b} refcount {r} != table "
                         f"occupancy {occupancy.get(b, 0)}")
                    assert (b in free_set) == (r == 0), \
                        (f"attn: block {b} ref {r} "
                         f"{'in' if b in free_set else 'not in'} free list")
                assert len(free) + len(set(held)) == n, \
                    f"attn: leak ({len(free)} free + {len(set(held))} held)"
            else:
                assert len(set(held)) == len(held), f"{g}: block shared"
                assert sorted(free + held) == list(range(1, n + 1)), \
                    f"{g}: leak ({len(free)} free + {len(held)} held != {n})"
        for blk, key in self._block_key.items():
            assert self._prefix_index.get(key) == blk, \
                f"index: block {blk} reverse-mapped to a stale key"
            assert self._ref[blk] >= 1, f"index: freed block {blk} indexed"
        assert len(self._prefix_index) == len(self._block_key), \
            "index: forward/reverse maps out of sync"
        for table, g in ((self.tables, "attn"), (self.swa_tables, "swa"),
                         (self.cross_tables, "cross")):
            for row in range(self.max_rows):
                ids = set(table[row].tolist()) - {0}
                assert ids <= set(self._held[g][row]), \
                    f"{g}: row {row} maps unheld blocks"


def paged_reset_row(caches, segs, row, cross_ids=None):
    """Zero decode row ``row``'s per-request state in a paged pytree:
    SSM/conv state rows, plus its cross-KV blocks (``cross_ids``, the
    row's cross-table entries) — scratch id 0 padding is harmless.
    Attn/swa pools are untouched (stale KV is position-masked)."""
    out = []
    for seg, c in zip(segs, caches):
        if seg.kind in ("mamba1", "mamba2"):
            c = jax.tree.map(lambda a: a.at[:, row].set(0), c)
        elif cross_ids is not None and ("xk" in c or "xv" in c):
            c = {k: (v.at[:, cross_ids].set(0) if k in ("xk", "xv") else v)
                 for k, v in c.items()}
        out.append(c)
    return out


def paged_copy_blocks(caches, segs, src, dst, *, has_swa: bool = False):
    """Apply queued copy-on-write pool copies to a paged pytree.

    ``src``/``dst`` are equal-length int arrays of physical attn-pool
    block ids (from :meth:`PagedCache.take_pending_copies`); each dst
    block becomes a byte-copy of its src block across every attn-pool
    k/v leaf.  Sharing is gated off for SWA/cross/SSM architectures, so
    only the shared attn pool ever needs copying; ``has_swa`` asserts
    that gate held (a windowless "swa" segment shares the attn pool in
    :meth:`PagedCache.struct` and is copied like one)."""
    assert not has_swa, "COW on an SWA architecture (sharing is gated off)"
    out = []
    for seg, c in zip(segs, caches):
        if seg.kind in ("attn", "swa"):
            c = {k: (v.at[:, dst].set(v[:, src]) if k in ("k", "v") else v)
                 for k, v in c.items()}
        out.append(c)
    return out
