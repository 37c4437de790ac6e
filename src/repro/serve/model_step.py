"""Model-step layer of the serving stack: slot-pool tensor state + the
prefill/decode/compress primitives, with NO request lifecycle.

This is the bottom half of the old monolithic ``serve/engine.py`` split
(DESIGN.md §15): everything that touches params, the KV cache, the
incremental per-slot sketches (serve/kv_compress.py, DESIGN.md §10/§12) and
the factored leaves lives here, as methods that transform the slot pool —
``prefill_rows`` (single-slot chunk at explicit positions, pool donated),
``decode_logits``/``sample`` (one batched decode step at the uniform slot
clock), ``compress_slot``/``auto_compress`` (dense-prefix -> FactoredKV
swaps), ``begin_slot`` (complete per-slot reset for a new tenant) and the
``kv_slot_bytes``/``kv_bytes_report`` HBM accounting.

Request queues, admission, chunked-prefill budgeting and SLO metrics live
above this layer: ``serve/scheduler.py`` is the production path (continuous
batching with catch-up contiguity), ``serve/engine.py`` the compat facade
that keeps the pre-split Engine API.

All jit'd shapes are static: (slots, max_seq).  The uniform slot clock
(decode writes every live slot's row at one shared ``write_pos``) is a
property of the decode step, not of this layer's bookkeeping — callers that
keep per-slot histories contiguous (scheduler catch-up) get compressible
slots; callers that don't (Engine's staggered admission) trip the
non-contiguity guard and serve dense (DESIGN.md §12.1).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.configs.base import ModelCfg
from repro.models import cache as cache_mod
from repro.models import registry as R
from repro.serve import kv_compress


def chunk_prefill(cfg: ModelCfg) -> bool:
    """Whether a prefill chunk runs as one batch-1 forward of all its
    tokens: every layer is self-attention over plain rows, one per
    position.  A windowed layer's cache is a ring buffer whatever the window
    (``models/cache``), and a ring, recurrent state, MLA latents and
    cross-attention take the chunk one token at a time instead."""
    return cfg.encdec is None and all(
        s.mixer == "attn" and not s.cross_attn and s.window is None
        for s in cfg.prelude + cfg.pattern)


def make_prefill_chunk(cfg: ModelCfg):
    """The jitted ``prefill_chunk(params, cache, tokens, start, slot) ->
    (cache, logits)`` and its path, ``"chunk"`` or ``"serial"``.  The program
    puts ``tokens`` (S,) at absolute positions ``start + arange(S)`` of pool
    slot ``slot`` (both int32 scalars, traced), returning the pool with that
    slot's new rows written and the (vocab,) f32 logits after the last
    token.  Only the slot's rows are read or written, batch-1, and the new
    rows are put back into the pool, which is donated, so they land in
    place.

    Under ``chunk_prefill`` the S tokens are one causal forward over the
    pool that reads the slot's rows layer by layer and returns only the S
    new rows; otherwise a token-serial scan runs the slot's rows, sliced
    out, and the whole slot goes back (ring, recurrent and latent state is
    not a row range).  A routed expert takes every token of the chunk that
    chose it, as it did when the chunk went one token at a time: the
    capacity factor is raised to the expert count, past any chunk's load."""
    if cfg.moe is not None:
        cfg = cfg.with_(moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.num_experts)))
    serve = R.make_serve_step(cfg)
    chunked = chunk_prefill(cfg)
    # the slot axis of each cache group: scan-stacked leaves lead with periods
    axes = {"pre": 0, "scan": 1, "rem": 0}

    def per_leaf(f, *trees):
        return {g: jax.tree.map(lambda *xs, ax=ax: f(ax, *xs),
                                *(t[g] for t in trees))
                for g, ax in axes.items()}

    def prefill_chunk(params, cache, tokens, start, slot):
        if chunked:
            logits, new = serve(params, {"tokens": tokens[None],
                                         "cache": cache, "write_pos": start,
                                         "cache_slot": slot})
            at_row = start
        else:
            rows = per_leaf(lambda ax, x: jax.lax.dynamic_slice_in_dim(
                x, slot, 1, ax), cache)

            def body(rows, tok_pos):
                tok, pos = tok_pos
                logits, rows = serve(params, {"tokens": tok.reshape(1, 1),
                                              "cache": rows,
                                              "write_pos": pos})
                return rows, logits
            new, logits = jax.lax.scan(
                body, rows, (tokens, start + jnp.arange(tokens.shape[0])))
            logits, at_row = logits[-1], 0

        def put(ax, pool, x):
            at = [0] * pool.ndim
            at[ax], at[ax + 1] = slot, at_row
            return jax.lax.dynamic_update_slice(pool, x, at)
        return per_leaf(put, cache, new), logits[0]

    return (jax.jit(prefill_chunk, donate_argnums=1),
            "chunk" if chunked else "serial")


class ModelStep:
    """Slot-pool model state + step primitives (see module docstring)."""

    def __init__(self, cfg: ModelCfg, params, *, slots: int = 4,
                 max_seq: int = 256, temperature: float = 0.0,
                 sample_seed: int = 0, kv_sketch_rank: Optional[int] = None,
                 kv_sketch_seed: int = 7,
                 kv_compress_ratio: Optional[float] = None):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.temperature = temperature
        self.key = jax.random.PRNGKey(sample_seed)
        self.cache = cache_mod.build_cache(cfg, slots, max_seq)
        self.pos = np.zeros(slots, np.int32)       # next write position
        self.last_logits: Optional[jax.Array] = None  # last decode step's
        self.readbacks = 0       # device-to-host reads made by ``readback``
        self._decode = jax.jit(R.make_serve_step(cfg))
        self._decode_masked = jax.jit(self._make_masked_decode())
        self._prefill_one, self._prefill_path = make_prefill_chunk(cfg)
        self.prefill_calls = {"chunk": 0, "serial": 0}
        # incremental KV compression (serve/kv_compress.py): per-slot,
        # per-cache-leaf streaming sketch states, appended as tokens land.
        self.kv_sketch_rank = kv_sketch_rank
        self._kv_key = jax.random.PRNGKey(kv_sketch_seed)
        linear_paths, ring_paths = self._find_kv_paths()
        self._kv_paths, self._kv_roll_paths = (
            (linear_paths, ring_paths) if kv_sketch_rank else ([], []))
        # windowed ring leaves, tracked even without sketching: begin_slot
        # must zero them for a new tenant (see its docstring)
        self._ring_paths = ring_paths
        self._kv_sketches: list[Optional[dict]] = [None] * slots
        # contiguous [start, count] span of cache rows not yet absorbed into
        # the sketches — decode only extends the span; the actual update
        # GEMMs run batched every _KV_FLUSH tokens or on kv_factors(), so
        # the jit'd decode hot loop pays no per-token sketch dispatch.
        self._kv_pending: list[Optional[list]] = [None] * slots
        self._kv_flush_every = 16
        # append-only watchdog: a slot whose rows ever land beyond its own
        # high-water mark (Engine's uniform-clock staggered admission) has a
        # gap the sketch never streamed.  Such histories must not compress
        # (comp_len would diverge from the sketch high-water; DESIGN §12.1).
        self._kv_next_row = np.zeros(slots, np.int64)
        self._kv_contig = [True] * slots
        # acting on the sketches (DESIGN.md §12): swap dense prefixes for
        # FactoredKV once the uncompressed span crosses ratio*rank rows.
        self.kv_compress_ratio = kv_compress_ratio
        self._kv_comp_len = np.zeros(slots, np.int32)
        self._kv_swap_paths = [p for p in self._kv_paths
                               if p[2] in ("k", "v")]
        self.kv_fact = None
        if kv_compress_ratio is not None:
            if not kv_sketch_rank:
                raise ValueError("kv_compress_ratio requires kv_sketch_rank")
            if kv_compress_ratio < 1.0:
                raise ValueError(f"kv_compress_ratio={kv_compress_ratio} "
                                 f"must be >= 1 (rows per factor rank)")
            if not self._kv_swap_paths:
                raise ValueError(
                    f"{cfg.name} has no full-context attention k/v leaves "
                    f"to compress (MLA latents / window-only stacks are not "
                    f"swappable — DESIGN.md §12)")
            self._kv_threshold = max(
                int(math.ceil(kv_compress_ratio * kv_sketch_rank)), 1)
            # a swap needs >= p streamed rows so Q's unseen rows (and hence
            # the factored prefix beyond comp_len) are exactly zero
            self._kv_min_rows = kv_compress._sketch_width(
                kv_sketch_rank, cfg.head_dim)
            self.kv_fact = cache_mod.build_kv_factors(
                cfg, slots, max_seq, kv_sketch_rank)

    # -- incremental KV sketching ------------------------------------------
    def _find_kv_paths(self) -> tuple[list, list]:
        """KV leaves of the cache eligible for incremental sketching, split
        by stream model: full-context attention k/v and MLA latent ckv/kr
        are append-only (linear SketchState); sliding-window k/v leaves
        (seq axis == window < max_seq) overwrite rows, so they get rolling
        sketches whose ring mirrors the cache ring (stream/rolling.py).
        Cross-attention histories stay skipped: static, nothing streams."""
        linear, rolling = [], []
        def classify(group, i, name, leaf):
            if name in ("k", "v"):
                if leaf.shape[-3] == self.max_seq:
                    linear.append((group, i, name))
                else:
                    rolling.append((group, i, name))
            elif name in ("ckv", "kr") and leaf.shape[-2] == self.max_seq:
                linear.append((group, i, name))
        for group in ("pre", "rem"):
            for i, layer in enumerate(self.cache[group] or ()):
                for name, leaf in layer.items():
                    classify(group, i, name, leaf)
        for i, layer in enumerate(self.cache["scan"] or ()):
            for name, leaf in layer.items():
                classify("scan", i, name, leaf)
        return linear, rolling

    def _kv_leaf_rows(self, path, slot: int, start: int, length: int):
        """(heads_batch, length, d) view of cache rows [start, start+len)."""
        group, i, name = path
        leaf = self.cache[group][i][name]
        if group == "scan":
            leaf = leaf[:, slot]                   # (periods, S, ...) view
        else:
            leaf = leaf[slot]
        if name in ("k", "v"):
            rows = leaf[..., start:start + length, :, :]
            rows = jnp.moveaxis(rows, -2, -3)      # (..., KV, T, hd)
        else:                                      # ckv/kr: (..., S, d)
            rows = leaf[..., start:start + length, :][..., None, :, :]
        return rows.reshape((-1,) + rows.shape[-2:])

    def _kv_leaf_rows_ring(self, path, slot: int, start: int, length: int):
        """(heads_batch, length, d) view of a WINDOWED leaf's cache rows for
        absolute history positions [start, start+length) — the cache ring
        holds position ``a`` in seq slot ``a % window``
        (transformer._attn_with_cache ring formula)."""
        group, i, name = path
        leaf = self.cache[group][i][name]
        leaf = leaf[:, slot] if group == "scan" else leaf[slot]
        window = leaf.shape[-3]
        idx = jnp.asarray((start + np.arange(length)) % window, jnp.int32)
        rows = jnp.take(leaf, idx, axis=leaf.ndim - 3)
        rows = jnp.moveaxis(rows, -2, -3)          # (..., KV, T, hd)
        return rows.reshape((-1,) + rows.shape[-2:])

    def _kv_roll_key(self, slot: int, j: int):
        return jax.random.fold_in(
            jax.random.fold_in(jax.random.fold_in(self._kv_key, slot),
                               0x7011), j)

    def _reset_slot_sketches(self, slot: int) -> None:
        sketches = {}
        for j, path in enumerate(self._kv_paths):
            rows = self._kv_leaf_rows(path, slot, 0, 1)
            key = jax.random.fold_in(jax.random.fold_in(self._kv_key, slot),
                                     j)
            sketches[path] = kv_compress.kv_sketch_init(
                key, rows.shape[0], rows.shape[-1], self.max_seq,
                self.kv_sketch_rank)
        for j, path in enumerate(self._kv_roll_paths):
            rows = self._kv_leaf_rows_ring(path, slot, 0, 1)
            group, i, name = path
            leaf = self.cache[group][i][name]
            window = (leaf[:, slot] if group == "scan"
                      else leaf[slot]).shape[-3]
            sketches[path] = kv_compress.kv_rolling_init(
                self._kv_roll_key(slot, j), rows.shape[0], rows.shape[-1],
                window, self.kv_sketch_rank)
        self._kv_sketches[slot] = sketches
        # new tenant: drop any compressed-prefix state the slot carried
        if self.kv_fact is not None and self._kv_comp_len[slot]:
            for path in self._kv_swap_paths:
                self._store_factors(slot, path, None)
            self._kv_comp_len[slot] = 0

    def begin_slot(self, slot: int) -> None:
        """Complete per-slot reset for a new tenant: next write position
        back to 0, the slot's windowed ring rows zeroed, and — when
        sketching is on — fresh sketch states (linear AND rolling ring),
        cleared pending span, contiguity watchdog rearmed and any
        factored-prefix leaves zeroed (via _reset_slot_sketches).
        Evict-then-readmit safety lives here: nothing of the previous
        tenant (ring rows, factors, comp_len, pending flush spans) may
        leak into the next request's stream.

        The ring zeroing is load-bearing, not hygiene: while a tenant's
        history is shorter than the window, the ring position formula
        (transformer._attn_with_cache) assigns the unwritten slots
        NEGATIVE kv positions, which still satisfy the window mask — a
        fresh cache holds zeros there and every windowed softmax includes
        them, so a reused slot must present the same zeros or the previous
        tenant's rows perturb each new hidden state.  Full-context leaves
        need no zeroing: rows beyond the tenant's pos sit outside the
        causal mask, and factor finalization masks rows the sketch never
        streamed (kv_compress._factor_one)."""
        with tracing.span("model_step.begin_slot"):
            self.pos[slot] = 0
            for path in self._ring_paths:
                group, i, name = path
                leaf = self.cache[group][i][name]
                if group == "scan":
                    self.cache[group][i][name] = leaf.at[:, slot].set(0)
                else:
                    self.cache[group][i][name] = leaf.at[slot].set(0)
            if self.kv_sketch_rank:
                self._reset_slot_sketches(slot)
                self._kv_pending[slot] = None
                self._kv_next_row[slot] = 0
                self._kv_contig[slot] = True

    def _append_slot_sketches(self, slot: int, start: int,
                              length: int) -> None:
        sk = self._kv_sketches[slot]
        for path in self._kv_paths:
            rows = self._kv_leaf_rows(path, slot, start, length)
            sk[path] = kv_compress.kv_sketch_append(sk[path], rows, start)
        if not self._kv_contig[slot]:
            # a slot with a gapped history (Engine staggered admission) sees
            # the uniform clock REGRESS below its high-water when longer-
            # running slots finish; rewriting ring history would corrupt the
            # eviction order, so its rolling sketches freeze at their last
            # synced state (the slot is excluded from compression anyway —
            # DESIGN.md §12.1)
            return
        for path in self._kv_roll_paths:
            # rows older than one window are dead on arrival (the cache ring
            # has already overwritten them): clamp the span to the trailing
            # window so the read is live and the tile fits the sketch ring
            end = start + length
            lo = max(start, end - sk[path].window)
            rows = self._kv_leaf_rows_ring(path, slot, lo, end - lo)
            sk[path] = kv_compress.kv_rolling_append(sk[path], rows, lo)

    def _note_kv_span(self, slot: int, start: int, length: int) -> None:
        """Record that cache rows [start, start+length) landed for ``slot``;
        flush the pending span through the sketch GEMMs only when it is long
        enough to amortize the dispatch (cache rows are append-only while a
        slot is live, so deferring the read is safe)."""
        if start != self._kv_next_row[slot]:
            self._kv_contig[slot] = False  # gap: rows skipped this slot
        self._kv_next_row[slot] = start + length
        pend = self._kv_pending[slot]
        if pend is None:
            self._kv_pending[slot] = [start, length]
        elif pend[0] + pend[1] == start:
            pend[1] += length
        else:                              # discontiguous: flush + restart
            self._flush_kv_pending(slot)
            self._kv_pending[slot] = [start, length]
        if self._kv_pending[slot][1] >= self._kv_flush_every:
            self._flush_kv_pending(slot)

    def _note_kv_row(self, slot: int, pos: int) -> None:
        self._note_kv_span(slot, pos, 1)

    def _flush_kv_pending(self, slot: int) -> None:
        pend = self._kv_pending[slot]
        if pend is None:
            return
        # fixed-size chunks keep the jitted update shapes to at most
        # _kv_flush_every variants (arbitrary prompt lengths would otherwise
        # compile a fresh executable per distinct span length per leaf)
        start, count = pend
        while count > 0:
            step = min(count, self._kv_flush_every)
            self._append_slot_sketches(slot, start, step)
            start += step
            count -= step
        self._kv_pending[slot] = None

    def kv_factors(self, slot: int) -> dict:
        """Rank-r FactoredKV per sketched cache leaf for ``slot``, finalized
        from the incrementally maintained sketches (no re-sketching).

        Full-context leaves factor against the slot's logical history (live
        dense rows, plus the reconstructed prefix once a compression swap
        has zeroed those rows — ``_kv_hist``); windowed leaves factor the
        current window from their rolling sketches."""
        if self._kv_sketches[slot] is None:
            raise ValueError(f"slot {slot} has no sketch state (engine "
                             f"built without kv_sketch_rank, or slot never "
                             f"admitted)")
        self._flush_kv_pending(slot)
        out = {}
        for path in self._kv_paths:
            out[path] = kv_compress.kv_sketch_factor(
                self._kv_sketches[slot][path], self._kv_hist(slot, path),
                self.kv_sketch_rank)
        for path in self._kv_roll_paths:
            out[path] = kv_compress.kv_rolling_factor(
                self._kv_sketches[slot][path],
                self._kv_ring_hist(slot, path), self.kv_sketch_rank)
        return out

    # -- acting on the sketches: compress / swap / account (DESIGN.md §12) --
    def _kv_hist(self, slot: int, path) -> jax.Array:
        """(heads_batch, max_seq, d) f32 logical history for a full-context
        leaf: the live dense rows plus, once rows [0, comp_len) have been
        swapped out (zeroed), the rank-r reconstruction of that prefix —
        ``us`` rows at/beyond comp_len are zero, so plain addition splices
        the two regions."""
        hist = self._kv_leaf_rows(path, slot, 0,
                                  self.max_seq).astype(jnp.float32)
        if (self.kv_fact is not None and self._kv_comp_len[slot]
                and path in self._kv_swap_paths):
            f = self._load_factors(slot, path)
            hist = hist + jnp.einsum("hsr,hrd->hsd", f.us, f.vt)
        return hist

    def _kv_ring_hist(self, slot: int, path) -> jax.Array:
        """(heads_batch, window, d) window-ordered history of a windowed
        leaf (oldest live row first) — what kv_rolling_factor expects."""
        window = self._kv_sketches[slot][path].window
        total = int(self._kv_sketches[slot][path].rows_seen.max())
        start = max(0, total - window)
        return self._kv_leaf_rows_ring(path, slot, start, window)

    def _fact_leaves(self, path):
        group, i, name = path
        return self.kv_fact[group][i], f"{name}_us", f"{name}_vt"

    def _store_factors(self, slot: int, path,
                       f: Optional[kv_compress.FactoredKV]) -> None:
        """Scatter one path's head-batched factors into the slot-batched
        factored leaves (None -> zero the slot's entries)."""
        tree, n_us, n_vt = self._fact_leaves(path)
        us, vt = tree[n_us], tree[n_vt]
        if path[0] == "scan":                # (periods, slots, KV, ...)
            if f is None:
                tree[n_us] = us.at[:, slot].set(0.0)
                tree[n_vt] = vt.at[:, slot].set(0.0)
            else:
                tree[n_us] = us.at[:, slot].set(
                    f.us.reshape(us.shape[:1] + us.shape[2:]))
                tree[n_vt] = vt.at[:, slot].set(
                    f.vt.reshape(vt.shape[:1] + vt.shape[2:]))
        else:                                # (slots, KV, ...)
            if f is None:
                tree[n_us] = us.at[slot].set(0.0)
                tree[n_vt] = vt.at[slot].set(0.0)
            else:
                tree[n_us] = us.at[slot].set(f.us.reshape(us.shape[1:]))
                tree[n_vt] = vt.at[slot].set(f.vt.reshape(vt.shape[1:]))

    def _load_factors(self, slot: int, path) -> kv_compress.FactoredKV:
        """Inverse of _store_factors: (heads_batch, S, r) / (heads_batch,
        r, d) views of the slot's stored factors."""
        tree, n_us, n_vt = self._fact_leaves(path)
        us, vt = tree[n_us], tree[n_vt]
        if path[0] == "scan":
            us, vt = us[:, slot], vt[:, slot]
            us = us.reshape((-1,) + us.shape[-2:])
            vt = vt.reshape((-1,) + vt.shape[-2:])
        else:
            us, vt = us[slot], vt[slot]
        return kv_compress.FactoredKV(us, vt)

    def _zero_dense_prefix(self, slot: int, path, pos: int) -> None:
        group, i, name = path
        leaf = self.cache[group][i][name]
        if group == "scan":                  # (periods, slots, S, KV, hd)
            self.cache[group][i][name] = leaf.at[:, slot, :pos].set(0)
        else:                                # (slots, S, KV, hd)
            self.cache[group][i][name] = leaf.at[slot, :pos].set(0)

    def compress_slot(self, slot: int) -> None:
        """Swap ``slot``'s dense rows [0, pos) for rank-r factors: finalize
        each full-context k/v leaf's factors from its incremental sketch,
        store them in the factored leaves the decode step attends through,
        zero the dense rows, and advance ``comp_len``.  New tokens keep
        appending to the dense tail; call again (or let the automatic
        ``kv_compress_ratio`` trigger fire) when the tail grows back.

        Raises ValueError when there is nothing to compress — an engine
        without ``kv_compress_ratio``, a never-admitted slot, a slot whose
        history is still shorter than the sketch width p (the zero-unseen-
        rows guarantee needs >= p streamed rows), or a slot with no new
        dense tail since the last swap (re-compression needs new rows; a
        second swap would only re-approximate the same factors).
        """
        if self.kv_fact is None:
            raise ValueError("engine built without kv_compress_ratio — "
                             "sketches are maintained but never acted on")
        if self._kv_sketches[slot] is None:
            raise ValueError(f"slot {slot} has no sketch state (never "
                             f"admitted)")
        self._flush_kv_pending(slot)
        pos = int(self.pos[slot])
        comp = int(self._kv_comp_len[slot])
        if pos - comp <= 0:
            raise ValueError(
                f"slot {slot} is already fully factored (comp_len == pos "
                f"== {pos}): re-compression needs newly appended dense-tail "
                f"rows")
        if pos < self._kv_min_rows:
            raise ValueError(
                f"slot {slot} has {pos} rows < sketch width "
                f"p={self._kv_min_rows}; compressing now would leave junk "
                f"in the factored rows beyond the history")
        if not self._kv_contig[slot]:
            raise ValueError(
                f"slot {slot} was admitted mid-stream: the uniform slot "
                f"clock wrote its decode rows beyond pos={pos}, so the "
                f"history has a gap the sketch never streamed — "
                f"compression requires an append-only contiguous history "
                f"(DESIGN.md §12.1)")
        with tracing.span("model_step.compress"):
            for path in self._kv_swap_paths:
                f = kv_compress.kv_sketch_factor(
                    self._kv_sketches[slot][path], self._kv_hist(slot, path),
                    self.kv_sketch_rank)
                self._store_factors(slot, path, f)
            for path in self._kv_swap_paths:
                self._zero_dense_prefix(slot, path, pos)
            self._kv_comp_len[slot] = pos

    def auto_compress(self, slot: int) -> None:
        """Fire the ``kv_compress_ratio`` trigger if the slot's dense tail
        has outgrown the threshold (no-op for gapped or too-short slots)."""
        if self.kv_fact is None or not self._kv_contig[slot]:
            return
        pos, comp = int(self.pos[slot]), int(self._kv_comp_len[slot])
        if pos - comp >= self._kv_threshold and pos >= self._kv_min_rows:
            self.compress_slot(slot)

    # back-compat spelling (pre-split Engine internals)
    _maybe_compress = auto_compress

    def kv_slot_bytes(self, slot: int) -> dict:
        """Per-slot HBM accounting over the swappable (full-context attn
        k/v) leaves: what a dense engine holds live for this slot vs what
        the compressed representation needs (dense tail + f32 factors).
        Representation bytes — the static pool itself cannot shrink at
        runtime; the win is pool capacity (DESIGN.md §12).  Zero for
        engines with nothing swappable (MLA latents are not k/v rows)."""
        pos = int(self.pos[slot])
        comp = int(self._kv_comp_len[slot])
        r = self.kv_sketch_rank or 0
        dense = held = 0
        for path in self._kv_swap_paths:
            group, i, name = path
            leaf = self.cache[group][i][name]
            lead = leaf.shape[0] if group == "scan" else 1
            kv, hd = leaf.shape[-2], leaf.shape[-1]
            item = jnp.dtype(leaf.dtype).itemsize
            dense += lead * kv * pos * hd * item
            held += lead * kv * (pos - comp) * hd * item
            if comp:
                held += lead * kv * kv_compress.factor_bytes(comp, r, hd)
        return {"slot": slot, "pos": pos, "comp_len": comp,
                "dense_bytes": dense, "compressed_bytes": held,
                "ratio": (held / dense) if dense else 1.0}

    def kv_bytes_report(self) -> dict:
        per_slot = [self.kv_slot_bytes(s) for s in range(self.slots)]
        return {
            "slots": per_slot,
            "dense_bytes": sum(r["dense_bytes"] for r in per_slot),
            "compressed_bytes": sum(r["compressed_bytes"]
                                    for r in per_slot),
        }

    def _make_masked_decode(self):
        """Decode step whose cache writes land only for slots in the mask.

        The plain serve step writes every slot's row at ``write_pos``; for
        the Engine that is harmless-by-convention (free slots get garbage a
        later whole-prompt prefill overwrites below its own pos, and the
        non-contiguity watchdog excludes such slots from compression).  The
        scheduler cannot accept it: a slot mid-chunked-prefill or catch-up
        would get a garbage row at the clock position — masked out of
        full-context attention by the causal mask, but aliased into LIVE
        window positions on sliding-window ring leaves (ring index
        clock % window can collide with a position <= the slot's own pos).
        Masking the cache merge keeps catching-up slots' histories exactly
        the rows they wrote themselves."""
        serve = R.make_serve_step(self.cfg)

        def mask_group(new, old, mask, axis):
            def f(n, o):
                if n is None:
                    return None
                shape = [1] * n.ndim
                shape[axis] = self.slots
                return jnp.where(mask.reshape(shape), n, o)
            return jax.tree.map(f, new, old)

        def decode_masked(params, batch, slot_mask):
            old = batch["cache"]
            logits, new = serve(params, batch)
            cache = {
                "pre": mask_group(new["pre"], old["pre"], slot_mask, 0),
                "scan": (mask_group(new["scan"], old["scan"], slot_mask, 1)
                         if old["scan"] is not None else None),
                "rem": mask_group(new["rem"], old["rem"], slot_mask, 0),
            }
            return logits, cache

        return decode_masked

    def prefill_rows(self, slot: int, tokens, start: int) -> jax.Array:
        """Run ``tokens`` through the single-slot prefill, writing cache rows
        [start, start + len(tokens)) for ``slot`` only, and return the
        (vocab,) logits row after the last token.  Advances the slot's
        ``pos`` and notes the rows with the sketch bookkeeping.

        This is the chunked-prefill primitive: the scheduler calls it with
        bounded-length chunks (each distinct length compiles one program,
        whatever the slot and start) and with single generated tokens
        during catch-up decode — both write at explicit absolute positions,
        so a slot driven only through this path stays contiguous.  The span
        carries ``path``: ``chunk`` (one forward of the chunk) or ``serial``
        (a token loop, for stacks ``chunk_prefill`` refuses)."""
        with tracing.span("model_step.prefill_rows",
                          path=self._prefill_path):
            toks = np.asarray(tokens, np.int32)
            logits = self._prefill(slot, toks, start)
            self.pos[slot] = start + int(toks.shape[0])
            if self.kv_sketch_rank:
                self._note_kv_span(slot, start, int(toks.shape[0]))
            return logits

    def _prefill(self, slot: int, toks: np.ndarray, start: int) -> jax.Array:
        """One call of the prefill program on the donated pool, counted in
        ``prefill_calls`` by its path; it returns once the program has run.
        The host arrays go to the device with the call itself, not as
        programs of their own.

        A slot or start outside the pool is refused here: the program would
        clamp it onto rows it was not given.  The wait keeps the program in
        its caller's span: the TPU runtime enqueues a program from its own
        thread after the call returns, so a profile would otherwise give the
        program's device time to whatever the host does next.  It costs
        little, since the scheduler reads back the logits of every call but
        a prompt's non-final chunks at once."""
        if toks.ndim != 1 or toks.shape[0] == 0:
            raise ValueError(f"prefill_rows takes a non-empty 1-D token "
                             f"chunk, got shape {toks.shape}")
        if not 0 <= slot < self.slots:
            raise ValueError(f"slot {slot} is not in the pool of "
                             f"{self.slots}")
        if start < 0 or start + toks.shape[0] > self.max_seq:
            raise ValueError(f"prefill of {toks.shape[0]} rows at "
                             f"{start} overruns max_seq={self.max_seq}")
        self.prefill_calls[self._prefill_path] += 1
        self.cache, logits = self._prefill_one(
            self.params, self.cache, toks, np.int32(start), np.int32(slot))
        return logits.block_until_ready()

    def decode_logits(self, tokens: np.ndarray, write_pos: int,
                      slot_mask=None) -> jax.Array:
        """One batched decode step over the pool at the uniform slot clock
        ``write_pos``.  Without ``slot_mask`` every slot's cache row lands
        at that position (Engine semantics); with a (slots,) bool mask only
        the masked slots' writes survive (scheduler semantics — see
        ``_make_masked_decode``).  Either way the caller decides which
        slots are live and must note their rows / advance their ``pos``.
        Returns (slots, vocab) f32 logits, device-resident (also kept as
        ``last_logits``)."""
        with tracing.span("model_step.decode_logits"):
            batch = {"tokens": jnp.asarray(tokens), "cache": self.cache,
                     "write_pos": jnp.asarray(write_pos, jnp.int32)}
            if self.kv_fact is not None:
                batch["kv_factors"] = self.kv_fact
                batch["comp_len"] = jnp.asarray(self._kv_comp_len)
            if slot_mask is None:
                logits, self.cache = self._decode(self.params, batch)
            else:
                logits, self.cache = self._decode_masked(
                    self.params, batch, jnp.asarray(slot_mask))
        self.last_logits = logits    # device-resident — consumers (tests,
        # probes) np.asarray it; the hot loop never does
        return logits

    def readback(self, x: jax.Array) -> np.ndarray:
        """``x`` on the host: the step path's one device-to-host read,
        counted in ``readbacks`` and spanned ``model_step.readback``."""
        self.readbacks += 1
        with tracing.span("model_step.readback"):
            return np.asarray(x)

    def sample(self, logits: jax.Array) -> np.ndarray:
        """(slots, vocab) logits -> (slots,) sampled token ids (greedy at
        temperature 0, categorical otherwise; consumes the sample key)."""
        if self.temperature > 0:
            self.key, sub = jax.random.split(self.key)
            nxt = jax.random.categorical(sub, logits / self.temperature)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        return self.readback(nxt)

    def pick(self, logits_row: jax.Array) -> int:
        """Next token from a single slot's (vocab,) logits — greedy, or
        temperature-sampled through the sample key (consumed in the same
        order a decode step would)."""
        row = self.readback(logits_row)
        if self.temperature > 0:
            return int(self.sample(row[None, :].repeat(self.slots,
                                                       axis=0))[0])
        return int(row.argmax())
