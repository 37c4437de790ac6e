"""Randomized SVD (paper Algorithm 1) with mixed-precision random projection.

The random projection (line 1, the O(mnp) term) is the paper's optimization
target; QR (line 2), B = Q^T A (line 3), tSVD (line 4) and the back-projection
(line 5) run in f32 (the cuSOLVER role is played by jnp.linalg).
"""

from __future__ import annotations

import functools
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import projection as proj


class SVDResult(NamedTuple):
    u: jax.Array      # (m, rank)
    s: jax.Array      # (rank,)
    vt: jax.Array     # (rank, n)


class AdaptiveInfo(NamedTuple):
    """Diagnostics of one adaptive ``rsvd_streamed(tol=...)`` run
    (DESIGN.md §13).  ``est_history`` holds the relative posterior error
    estimate after each B pass (one entry per evaluated width);
    ``bound_history`` the matching relative Halko Eq. (4) expected-error
    bound — None where the width leaves oversample < 2, and None at EVERY
    width for non-Gaussian families (Eq. 4 is a theorem about Gaussian test
    matrices; ``bound_reason`` carries the documented reason from
    ``core.structured.ESTIMATOR_VALIDITY``, None when the bound applies).
    The byte counters are what the widen passes actually wrote to Y
    (``grown_sketch_bytes``) vs what re-sketching from scratch at each
    grown width would have written (``full_resketch_bytes``) — the
    added-columns-only scaling the bench asserts."""
    final_p: int
    widen_passes: int
    converged: bool
    est_history: tuple
    bound_history: tuple
    grown_cols: int
    grown_sketch_bytes: int
    full_resketch_bytes: int
    bound_reason: str | None = None


def _dot(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _check_rank(rank: int, m: int, n: int) -> None:
    """Target ranks above min(m, n) used to be silently absorbed by the
    ``p_hat = min(rank + oversample, min(m, n))`` clamp and then sliced as
    ``u[:, :rank]`` — returning an under-ranked factorization with no
    warning.  Shapes and rank are static, so this raises at trace time,
    under jit included."""
    if not 1 <= rank <= min(m, n):
        raise ValueError(
            f"rank={rank} is out of range for a {m}x{n} matrix: need "
            f"1 <= rank <= min(m, n) = {min(m, n)} — the sketch-width clamp "
            f"would otherwise silently return only min(m, n) columns")


@functools.partial(
    jax.jit,
    static_argnames=("rank", "oversample", "power_iters", "method", "dist",
                     "omega_dtype"),
)
def rsvd(key: jax.Array, a: jax.Array, rank: int, *, oversample: int = 10,
         power_iters: int = 0, method: proj.ProjectionMethod = "shgemm",
         dist: proj.SketchDist = "gaussian",
         omega_dtype=jnp.bfloat16) -> SVDResult:
    """p-rank randomized SVD of ``a`` (paper Algorithm 1).

    oversample: the paper's s (they fix s=10 in §5.1); the sketch width is
    p_hat = rank + oversample.
    power_iters: q power iterations (A A^T)^q A Omega for slowly decaying
    spectra (§2.1); the extra passes run in f32.
    dist: Omega family — unstructured (gaussian/achlioptas/very_sparse) or
    ``"srht"``, which replaces the line-1 GEMM with the O(n log n)
    structured apply (core/structured.py).
    """
    m, n = a.shape
    _check_rank(rank, m, n)
    p_hat = min(rank + oversample, min(m, n))

    # Each line runs under a named scope (repro.tracing.SCOPES): metadata
    # only, so a device trace's operations group by line.
    # Line 1: Y = A . Omega — THE mixed-precision projection.  Key-based:
    # with method="shgemm_fused" Omega is generated inside the kernel and
    # never materialized (zero HBM bytes for the random matrix).
    with jax.named_scope("rsvd.sketch"):
        y = proj.sketch(key, a, p_hat, method=method, dist=dist,
                        omega_dtype=omega_dtype)

    # Power scheme: re-orthonormalize between passes for stability.
    with jax.named_scope("rsvd.power"):
        for _ in range(power_iters):
            q, _ = jnp.linalg.qr(y)
            z = _dot(a.T, q)
            q, _ = jnp.linalg.qr(z)
            y = _dot(a, q)

    # Line 2: thin QR.
    with jax.named_scope("rsvd.qr"):
        q, _ = jnp.linalg.qr(y)
    # Line 3: B = Q^T A  (p_hat x n).
    with jax.named_scope("rsvd.project_b"):
        b = _dot(q.T, a)
    # Line 4: tSVD of the small matrix.
    with jax.named_scope("rsvd.small_svd"):
        u_b, s, vt = jnp.linalg.svd(b, full_matrices=False)
    # Line 5: U = Q . U', and the truncation to ``rank``.
    with jax.named_scope("rsvd.lift_u"):
        u = _dot(q, u_b)
        return SVDResult(u[:, :rank], s[:rank], vt[:rank, :])


def rsvd_streamed(key: jax.Array, a_blocks, rank: int, *,
                  n_rows: int | None = None, n_cols: int | None = None,
                  oversample: int = 10, passes: int = 2,
                  method: proj.ProjectionMethod = "shgemm_fused",
                  dist: proj.SketchDist = "gaussian",
                  omega_dtype=jnp.bfloat16, tile_callback=None,
                  prefetch_depth: int | None = 1,
                  tol: float | None = None,
                  max_oversample: int | None = None,
                  return_info: bool = False,
                  checkpoint_dir=None,
                  checkpoint_every_tiles: int | None = None,
                  resume: bool = False,
                  return_report: bool = False):
    """Randomized SVD of an out-of-core matrix streamed as row tiles.

    ``a_blocks`` is anything ``stream.as_tile_source`` accepts: a
    ``TileSource`` (in-memory array, ``.npy`` memmap, directory of ``.npy``
    shards, generator factory), a plain sequence of row tiles, a zero-arg
    callable returning a fresh tile iterator, or — for ``passes=1`` only —
    a bare one-shot generator.  ``n_rows``/``n_cols`` may be omitted when
    the source knows its shape (everything but bare generators/callables).
    Tiles are double-buffer prefetched (background IO + host→device overlap,
    ``prefetch_depth=None`` disables).  Never holds more than
    ``prefetch_depth + 1`` tiles of A plus O((m+n)·p) sketch/factor state;
    the sketch accumulates through ``repro.stream``, so Omega costs zero
    HBM bytes with ``method="shgemm_fused"`` and each tile's sketch rows
    are bit-identical to one-shot sketching of the concatenated matrix.

    ``passes`` = number of streams over the tiles (DESIGN.md §11.3):

      * 1 — strict single pass, finalized from the (Y, W) sketches alone
        (Tropp et al. 2017); loosest accuracy, for unreplayable streams.
      * 2 (default) — sketch, orthonormalize to Q, replay once for
        B = Q^T A: numerically identical to ``rsvd(power_iters=0)`` up to
        f32 summation order.
      * >= 3 — streamed power iteration on the replayable source: each
        extra pass applies one more A (alternating Z = A^T·Q and Y = A·Z
        with re-orthonormalization, A never materialized).
        ``passes = 2 + 2q`` reproduces ``rsvd(power_iters=q)``'s exact
        iteration; odd counts finalize from the column basis via
        A·Z = Q·R ⇒ A ≈ Q·R·Z^T at no extra pass.  Bit-deterministic for
        a fixed tiling: pass 1 draws Omega from the fused
        (key, global offset) lattice, and every later pass is a plain
        tiled GEMM accumulated in tile order.

    ``tile_callback(i, n_seen_rows)``, if given, is invoked per absorbed
    tile of the initial sketch pass (progress for multi-hour out-of-core
    runs).

    Adaptive rank-revealing mode (``tol=...``, DESIGN.md §13): instead of
    trusting the fixed paper oversampling (s=10, §5.1), grow the sketch
    width between passes until the rank-``rank`` truncation error is
    certified under ``tol``.  After each B = QᵀA pass the driver knows the
    error EXACTLY (Q orthonormal ⇒ ||A - Q·[B]_r||_F² = ||A||_F² -
    Σ_{i<=r} σ_i(B)², with ||A||_F² accumulated during the sketch pass);
    ``tol`` is that error relative to ||A||_F.  While the estimate exceeds
    ``tol``, the sketch width doubles its oversampling (capped at
    ``rank + max_oversample`` and min(m, n)): with
    ``method="shgemm_fused"`` the new Omega columns are sketched on a
    replay pass via ``SketchState.widen`` — work proportional to the
    ADDED columns, and the grown state is bit-identical to a fresh sketch
    at the final width (global-lattice Omega); legacy methods re-sketch at
    the new width (jax.random draws are shape-dependent), equally
    bit-identical to fresh, just not incremental.  Requires ``passes=2``
    (each evaluation is one widen replay + one B replay, so a run that
    widens k times streams the tiles 2 + 2k times) and a replayable
    source.  ``return_info=True`` additionally returns an
    :class:`AdaptiveInfo` with the widen/byte counters and the
    estimate + Halko-bound histories.  Numerics: the estimate is exact in
    exact arithmetic and monotone non-increasing in the width for the
    fused lattice (nested sketch subspaces), but the f32 cancellation
    ``||A||² - Σσ²`` floors it near sqrt(eps)·||A||_F ≈ 3.5e-4 relative —
    a ``tol`` below that floor just widens to the cap.

    Fault tolerance (``checkpoint_dir=...``, DESIGN.md §14): checkpoint
    the sketch state + tile cursor every ``checkpoint_every_tiles`` tiles
    (atomic + async, same discipline as ``train/checkpoint.py``) so a
    killed job restarted with ``resume=True`` continues from the last
    checkpoint instead of from scratch.  The cursor is always a tile
    boundary and the replay preserves the original tile order, so the
    resumed result is **bitwise equal** to the uninterrupted run, with at
    most ``checkpoint_every_tiles`` tiles recomputed during the sketch and
    B passes (power passes for ``passes >= 3`` checkpoint at pass
    boundaries — one pass of recomputation worst case).  ``resume=True``
    with an empty directory is a fresh start, so one command line serves
    attempt 1 and every retry; a checkpoint written under a different
    key/rank/method/shape fails loudly (fingerprint mismatch).  Requires a
    replayable source; incompatible with adaptive mode (``tol=`` owns a
    data-dependent pass schedule).  ``return_report=True`` additionally
    returns a :class:`repro.stream.resilience.ResilienceReport` (attempts,
    goodput, tiles recomputed, recovery events).
    """
    from repro import stream  # deferred: stream imports this module's result
    if passes < 1:
        raise ValueError(f"passes must be >= 1, got {passes}")
    if tol is not None:
        tol = float(tol)
        if tol <= 0.0:
            raise ValueError(f"tol must be > 0, got {tol}")
        if passes != 2:
            raise ValueError(
                f"adaptive mode (tol=) owns the pass schedule — it runs "
                f"2 + 2*(widen rounds) passes — so passes must stay at its "
                f"default 2, got passes={passes}")
    if max_oversample is not None:
        if tol is None:
            raise ValueError("max_oversample only applies to adaptive "
                             "(tol=...) runs")
        max_oversample = int(max_oversample)
        if max_oversample < 0:
            raise ValueError(f"max_oversample must be >= 0, got "
                             f"{max_oversample}")
    if return_info and tol is None:
        raise ValueError("return_info=True only applies to adaptive "
                         "(tol=...) runs")
    if checkpoint_dir is None:
        if checkpoint_every_tiles is not None:
            raise ValueError("checkpoint_every_tiles needs checkpoint_dir=")
        if resume:
            raise ValueError("resume=True needs checkpoint_dir= (there is "
                             "nowhere to resume from)")
        if return_report:
            raise ValueError("return_report=True needs checkpoint_dir= "
                             "(the report measures the checkpointed job)")
    elif tol is not None:
        raise ValueError(
            "checkpoint_dir is incompatible with adaptive mode (tol=): "
            "the widen schedule is data-dependent, so a resumed run could "
            "not prove it replays the identical pass sequence — run "
            "adaptive jobs without checkpointing, or checkpoint a "
            "fixed-oversample job")
    shape = ((int(n_rows), int(n_cols))
             if n_rows is not None and n_cols is not None else None)
    try:
        src = stream.as_tile_source(a_blocks, shape=shape)
    except ValueError as e:
        if shape is None and "shape" in str(e):
            # translate the internal shape= requirement into this API's
            # kwargs — a single n_rows or n_cols alone is not enough
            raise ValueError(
                "this tile stream cannot be inspected for its shape: pass "
                "BOTH n_rows= and n_cols= (or stream from a "
                "TileSource/array/.npy path, which knows its shape)") from e
        raise
    if n_rows is not None and int(n_rows) != src.n_rows:
        raise ValueError(f"n_rows={n_rows} but the tile source has "
                         f"{src.n_rows} rows")
    if n_cols is not None and int(n_cols) != src.n_cols:
        raise ValueError(f"n_cols={n_cols} but the tile source has "
                         f"{src.n_cols} columns")
    n_rows, n_cols = src.n_rows, src.n_cols
    if passes >= 2 and not src.replayable:
        # fail BEFORE streaming: a bare generator would be consumed by the
        # first pass and the error would otherwise land hours into an
        # out-of-core run
        raise ValueError(
            f"passes={passes} must replay the tile stream: pass a "
            "replayable TileSource (array / memmap / directory-of-npy / "
            "zero-arg factory) or a sequence of tiles (or use passes=1 "
            "for the strict single-pass finalizer)")

    ck = None   # bound below; tiles() reads it through the closure

    def tiles(start_tile=0, start_row=0):
        # Resume contract: tiles_from yields the EXACT suffix of the full
        # tiling (same boundaries, same order), so every f32 accumulation
        # downstream sees the same operand sequence as an uninterrupted
        # run — the bitwise-resume guarantee.  The post-yield note_tile
        # times the CONSUMER's absorption of each tile (generator resumes
        # when the next tile is requested).
        off = start_row
        it = stream.source_tiles(src, prefetch_depth=prefetch_depth,
                                 start_row=start_row)
        if ck is not None:
            it = ck.guard(it)
        t_last = time.perf_counter()
        for i, blk in enumerate(it, start=start_tile):
            yield i, off, blk
            off += blk.shape[0]
            if ck is not None:
                now = time.perf_counter()
                ck.note_tile(now - t_last)
                t_last = now
        if off != n_rows:
            raise ValueError(f"tiles cover {off} rows, expected {n_rows}")

    _check_rank(rank, n_rows, n_cols)
    minmn = min(n_rows, n_cols)
    p_cap = minmn
    if max_oversample is not None:
        p_cap = min(p_cap, rank + max_oversample)
    p_hat = min(rank + oversample, p_cap if tol is not None else minmn)

    restored = None
    if checkpoint_dir is not None:
        from repro.stream import resilience as resil
        if not src.replayable:
            raise ValueError(
                "checkpoint_dir needs a replayable tile source: resuming "
                "replays the tile suffix after the checkpointed cursor, "
                "which a one-shot generator cannot provide")
        fingerprint = {
            "job": "rsvd_streamed",
            "key": resil.key_fingerprint(key),
            "rank": int(rank), "p_hat": int(p_hat), "passes": int(passes),
            "method": str(method), "dist": str(dist),
            "omega_dtype": str(jnp.dtype(omega_dtype)),
            "n_rows": int(n_rows), "n_cols": int(n_cols),
        }
        ck = resil.SketchJobCheckpointer(
            checkpoint_dir,
            every_tiles=(16 if checkpoint_every_tiles is None
                         else checkpoint_every_tiles),
            fingerprint=fingerprint, resume=resume)
        restored = ck.restore()

    def done(res):
        if ck is None:
            return res
        report = ck.finish(
            tiles_total=(resil._count_tiles(src) or 0) * passes)
        return (res, report) if return_report else res

    start_tile = start_row = 0
    b_resume = power_resume = None
    if restored is not None:
        if restored.phase == "sketch":
            state = resil.state_from_payload(restored.arrays, restored.meta)
            start_tile, start_row = restored.tiles_done, restored.rows_done
        elif restored.phase == "b":
            state = resil.state_from_payload(restored.arrays, restored.meta)
            b_resume = (jnp.asarray(restored.arrays["b"]),
                        restored.tiles_done, restored.rows_done)
        elif restored.phase == "power":
            power_resume = restored
        else:
            raise RuntimeError(f"checkpoint under {checkpoint_dir} is in "
                               f"unknown phase {restored.phase!r}")
    if restored is None:
        state = stream.init(key, n_cols, p_hat, max_rows=n_rows,
                            left=(passes == 1), method=method, dist=dist,
                            omega_dtype=omega_dtype)

    fro2 = jnp.zeros((), jnp.float32)   # ||A||_F² for the posterior estimate
    if b_resume is None and power_resume is None:
        tiles_done, rows_done = start_tile, start_row
        for i, off, blk in tiles(start_tile, start_row):
            state = stream.update(state, blk, off)
            if tol is not None:
                fro2 = fro2 + jnp.sum(jnp.square(blk.astype(jnp.float32)))
            if tile_callback is not None:
                tile_callback(i, off + blk.shape[0])
            tiles_done, rows_done = i + 1, off + int(blk.shape[0])
            if ck is not None:
                ck.tick(phase="sketch", pass_idx=1, tiles_done=tiles_done,
                        rows_done=rows_done,
                        payload=lambda s=state: resil.state_to_payload(s))
        if ck is not None:
            # pass boundary: never re-enter the sketch phase on resume
            ck.commit(phase="sketch", pass_idx=1, tiles_done=tiles_done,
                      rows_done=rows_done,
                      payload=lambda: resil.state_to_payload(state))
    if passes == 1:
        return done(stream.svd(state, rank))

    def accumulate_b(q):
        b = jnp.zeros((q.shape[1], n_cols), jnp.float32)
        for _, off, blk in tiles():                    # B = Q^T A, tiled
            b = b + _dot(q[off:off + blk.shape[0]].T,
                         blk.astype(jnp.float32))
        return b

    if tol is not None:
        return _adaptive_rsvd(
            stream, key, state, rank, tol=tol, p_cap=p_cap, fro2=fro2,
            tiles=tiles, accumulate_b=accumulate_b, n_rows=n_rows,
            n_cols=n_cols, method=method, dist=dist,
            omega_dtype=omega_dtype, return_info=return_info)

    if ck is not None and passes == 2 and power_resume is None:
        # checkpointed B pass, tile granularity: B's f32 summation is
        # order-sensitive, so the partial B + cursor is the checkpoint and
        # the replay appends the identical remaining terms.  Q is NOT
        # stored: it is recomputed from the (checkpointed) sketch state,
        # deterministically.  Same algebra as streamed_power_factor's
        # final on-rows branch.
        q = stream.range_basis(state)
        if b_resume is not None:
            b, tiles_done, rows_done = b_resume
        else:
            b = jnp.zeros((q.shape[1], n_cols), jnp.float32)
            tiles_done, rows_done = 0, 0

        def b_payload(bb):
            arrays, meta = resil.state_to_payload(state)
            arrays["b"] = np.asarray(bb)
            return arrays, meta

        for i, off, blk in tiles(tiles_done, rows_done):
            b = b + _dot(q[off:off + blk.shape[0]].T,
                         blk.astype(jnp.float32))
            tiles_done, rows_done = i + 1, off + int(blk.shape[0])
            ck.tick(phase="b", pass_idx=2, tiles_done=tiles_done,
                    rows_done=rows_done,
                    payload=lambda bb=b: b_payload(bb))
        u_b, s, vt = jnp.linalg.svd(b, full_matrices=False)
        u = _dot(q, u_b)
        return done(SVDResult(u[:, :rank], s[:rank], vt[:rank, :]))

    def accumulate_y(z):
        # tiles cover the rows in order, so Y = A·Z is the concatenation of
        # per-tile products — O(m·p) total, where an eager .at[].set per
        # tile would copy the whole Y buffer n_tiles times
        return jnp.concatenate([_dot(blk.astype(jnp.float32), z)
                                for _, _, blk in tiles()], axis=0)

    on_pass_done = None
    if ck is not None:
        def on_pass_done(pass_idx, which, basis):
            # power passes checkpoint at pass boundaries: each basis is a
            # full orthonormal factor, so a resume replays at most one
            # pass (documented relaxation of the per-tile bound)
            ck.commit(phase="power", pass_idx=pass_idx, tiles_done=0,
                      rows_done=0,
                      payload=lambda: ({"basis": np.asarray(basis)},
                                       {"power": {"which": which}}))

    if power_resume is not None:
        basis = jnp.asarray(power_resume.arrays["basis"])
        which = power_resume.meta["power"]["which"]
        return done(streamed_power_factor(
            basis if which == "q" else None, rank, passes,
            accumulate_b=accumulate_b, accumulate_y=accumulate_y,
            start_pass=power_resume.pass_idx + 1,
            z=basis if which == "z" else None,
            start_on_rows=(which == "q"), on_pass_done=on_pass_done))

    return done(streamed_power_factor(stream.range_basis(state), rank,
                                      passes, accumulate_b=accumulate_b,
                                      accumulate_y=accumulate_y,
                                      on_pass_done=on_pass_done))


def _adaptive_rsvd(stream, key, state, rank, *, tol, p_cap, fro2, tiles,
                   accumulate_b, n_rows, n_cols, method, dist, omega_dtype,
                   return_info):
    """Rank-revealing widening loop behind ``rsvd_streamed(tol=...)``
    (DESIGN.md §13).  One B = QᵀA replay per evaluated width gives the
    EXACT truncation error; while it exceeds ``tol`` the sketch doubles
    its oversampling — incrementally (``SketchState.widen`` + replay over
    only the new Omega columns) for the fused lattice, by re-sketching at
    the new width for legacy jax.random streams AND for SRHT (every SRHT
    entry carries a 1/sqrt(p) scale tied to the total width, so there are
    no shared columns to extend).  Either way the working state stays
    bit-identical to a fresh sketch at its width, so the final
    factorization equals the non-adaptive two-pass run at the final
    oversampling bit for bit.

    Estimator validity (DESIGN.md §17): the stopping rule above is the
    EXACT posterior estimate — valid for every Omega family (it only needs
    Q orthonormal).  The Halko Eq. (4) diagnostic is a Gaussian-family
    theorem, so it is reported only for ``dist="gaussian"``; other families
    get None entries plus the documented reason in
    ``AdaptiveInfo.bound_reason`` (core.structured.ESTIMATOR_VALIDITY).
    """
    from repro.core import structured as _sx
    fro2 = jnp.maximum(fro2, jnp.float32(0))
    bound_ok = _sx.halko_bound_valid(dist)
    est_hist, bound_hist = [], []
    widen_passes = grown_cols = grown_bytes = full_bytes = 0
    while True:
        q = stream.range_basis(state)
        b = accumulate_b(q)
        u_b, sv, vt = jnp.linalg.svd(b, full_matrices=False)
        head2 = jnp.sum(jnp.square(sv[:rank]))
        denom = jnp.sqrt(jnp.maximum(fro2, jnp.float32(1e-30)))
        est = float(jnp.sqrt(jnp.maximum(fro2 - head2, 0.0)) / denom)
        est_hist.append(est)
        s_now = state.p - rank
        bound_hist.append(
            float(halko_bound(jnp.linalg.norm(sv[rank:]), rank, s_now)
                  / denom) if bound_ok and s_now >= 2 else None)
        converged = est <= tol
        if converged or state.p >= p_cap:
            break
        extra = min(state.p, p_cap - state.p)   # double the width, capped
        p_new = state.p + extra
        if method == "shgemm_fused" and dist != "srht":
            # replay sketches ONLY the new lattice columns: O(extra) work
            ext = state.widen(extra)
            for _, off, blk in tiles():
                ext = stream.update(ext, blk, off)
            state = stream.hstack(state, ext)
            grown_bytes += 4 * n_rows * extra
        else:
            # legacy jax.random Omega is a function of its full shape (and
            # SRHT of its full width) — a fresh draw at p_new shares no
            # columns with the old one, so bit-identity to a fresh sketch
            # demands a full re-sketch
            state = stream.init(key, n_cols, p_new, max_rows=n_rows,
                                method=method, dist=dist,
                                omega_dtype=omega_dtype)
            for _, off, blk in tiles():
                state = stream.update(state, blk, off)
            grown_bytes += 4 * n_rows * p_new
        full_bytes += 4 * n_rows * p_new
        grown_cols += extra
        widen_passes += 1
    u = _dot(q, u_b)
    res = SVDResult(u[:, :rank], sv[:rank], vt[:rank, :])
    if not return_info:
        return res
    return res, AdaptiveInfo(
        final_p=state.p, widen_passes=widen_passes, converged=converged,
        est_history=tuple(est_hist), bound_history=tuple(bound_hist),
        grown_cols=grown_cols, grown_sketch_bytes=grown_bytes,
        full_resketch_bytes=full_bytes,
        bound_reason=_sx.bound_invalid_reason(dist))


def streamed_power_factor(q: jax.Array, rank: int, passes: int, *,
                          accumulate_b, accumulate_y, start_pass: int = 2,
                          z: jax.Array | None = None,
                          start_on_rows: bool = True,
                          on_pass_done=None) -> SVDResult:
    """Shared multi-pass driver for streamed power iteration
    (DESIGN.md §11.3): alternate row-space basis Q (m, p) and column-space
    basis Z (n, p), one stream over the tiles per pass, starting from the
    orthonormal sketch basis ``q``.  The B = Q^T A accumulation doubles as
    Z = A^T Q = B^T, so each power half-step costs exactly one pass; an
    odd final pass factorizes from the column basis for free via
    A·Z = Q·R ⇒ A ≈ A Z Z^T = Q R Z^T (Z orthonormal).

    ``accumulate_b(q)`` streams once and returns B = Q^T A (p, n);
    ``accumulate_y(z)`` streams once and returns Y = A·Z (m, p).  The
    callbacks own distribution: single-host tile loops in
    ``rsvd_streamed``, per-host partials + one psum in
    ``distributed_rsvd_streamed`` — both share this exact algebra, so the
    two paths cannot drift numerically.

    Resume hooks (DESIGN.md §14): each non-final pass ends in exactly one
    orthonormal basis — Q after an off-rows pass, Z after an on-rows
    pass — which is the pass's complete successor state.
    ``on_pass_done(pass_idx, which, basis)`` (``which`` in ``{"q", "z"}``)
    hands it to a checkpointer; a killed job re-enters the iteration
    mid-schedule via ``start_pass`` + the saved basis (``q`` +
    ``start_on_rows=True`` or ``z`` + ``start_on_rows=False``), bitwise
    equal to the uninterrupted schedule because each pass is a pure
    function of its entry basis and the tile stream.
    """
    on_rows = start_on_rows
    if on_rows and q is None:
        raise ValueError("start_on_rows=True needs the row basis q")
    if not on_rows and z is None:
        raise ValueError("start_on_rows=False needs the column basis z")
    for pass_idx in range(start_pass, passes + 1):
        last = pass_idx == passes
        if on_rows:
            b = accumulate_b(q)
            if last:
                u_b, s, vt = jnp.linalg.svd(b, full_matrices=False)
                u = _dot(q, u_b)
                return SVDResult(u[:, :rank], s[:rank], vt[:rank, :])
            z, _ = jnp.linalg.qr(b.T)                  # orth(A^T Q)
            on_rows = False
            if on_pass_done is not None:
                on_pass_done(pass_idx, "z", z)
        else:
            y = accumulate_y(z)
            if last:
                q, r = jnp.linalg.qr(y)
                u_r, s, wt = jnp.linalg.svd(r, full_matrices=False)
                return SVDResult(_dot(q, u_r)[:, :rank], s[:rank],
                                 _dot(wt, z.T)[:rank, :])
            q, _ = jnp.linalg.qr(y)
            on_rows = True
            if on_pass_done is not None:
                on_pass_done(pass_idx, "q", q)
    raise AssertionError("unreachable")  # loop always returns on last pass


@functools.partial(jax.jit, static_argnames=("rank", "oversample", "method",
                                             "dist", "omega_dtype"))
def range_finder(key: jax.Array, a: jax.Array, rank: int, *, oversample: int = 10,
                 method: proj.ProjectionMethod = "shgemm",
                 dist: proj.SketchDist = "gaussian",
                 omega_dtype=jnp.bfloat16) -> jax.Array:
    """Return Q with orthonormal columns s.t. A ~ Q Q^T A (Eq. 3)."""
    m, n = a.shape
    _check_rank(rank, m, n)
    p_hat = min(rank + oversample, min(m, n))
    y = proj.sketch(key, a, p_hat, method=method, dist=dist,
                    omega_dtype=omega_dtype)
    q, _ = jnp.linalg.qr(y)
    return q


def projection_error(a: jax.Array, q: jax.Array) -> jax.Array:
    """||A - Q Q^T A||_F — the Fig. 3 / Eq. 4 quantity."""
    a = a.astype(jnp.float32)
    resid = a - _dot(q, _dot(q.T, a))
    return jnp.linalg.norm(resid)


def reconstruction_error(a: jax.Array, res: SVDResult) -> jax.Array:
    """Relative residual ||A - U S V^T||_F / ||A||_F (Fig. 7 metric)."""
    a = a.astype(jnp.float32)
    approx = _dot(res.u * res.s[None, :], res.vt)
    return jnp.linalg.norm(a - approx) / jnp.linalg.norm(a)


def halko_bound(s_tail_norm: jax.Array, rank: int, oversample: int) -> jax.Array:
    """Expected-error bound Eq. (4): sqrt(1 + p/(s-1)) * ||Sigma_2||_F.

    Domain: Eq. (4) (Halko et al. 2011, Thm. 10.5's expectation) averages
    over s - 1 degrees of freedom, so it requires ``oversample >= 2``: at
    s = 1 the prefactor divides by zero (the expectation genuinely
    diverges) and below that the sqrt argument goes negative — both used
    to leak inf/NaN into callers instead of failing."""
    if oversample < 2:
        raise ValueError(
            f"halko_bound needs oversample >= 2 (Eq. 4's expectation runs "
            f"over s-1 degrees of freedom and diverges at s=1; below that "
            f"the sqrt argument is negative), got oversample={oversample}")
    return jnp.sqrt(1.0 + rank / (oversample - 1.0)) * s_tail_norm


@functools.partial(jax.jit, static_argnames=("rank", "oversample", "method",
                                             "omega_dtype"))
def nystrom_eigh(key: jax.Array, a: jax.Array, rank: int, *,
                 oversample: int = 10, method: proj.ProjectionMethod = "shgemm",
                 omega_dtype=jnp.bfloat16) -> tuple[jax.Array, jax.Array]:
    """Randomized Nystrom eigendecomposition of a PSD matrix (RandNLA
    family extension; Halko et al. §5.4 / Tropp et al. 2017).

    A ~ U diag(lam) U^T with a single mixed-precision projection pass:
      Y = A Omega  (the paper's hot GEMM), nu-shifted for stability,
      C = chol(Omega^T Y), B = Y C^-T, SVD(B) -> U, lam = sig^2 - nu.
    """
    n = a.shape[0]
    _check_rank(rank, n, a.shape[1])
    p_hat = min(rank + oversample, n)
    # Nystrom reuses Omega downstream (shift + Gram), so it must exist in
    # HBM; with the fused method the hot GEMM still skips the Omega reads
    # and fused_omega reproduces the identical in-kernel stream for the
    # small downstream terms.
    if method == "shgemm_fused":
        omega = proj.fused_omega(key, (n, p_hat), dtype=omega_dtype)
    else:
        omega = proj.gaussian(key, (n, p_hat), dtype=omega_dtype)
    y = proj.sketch(key, a, p_hat, method=method,
                    omega_dtype=omega_dtype)              # (n, p_hat)
    nu = jnp.sqrt(jnp.asarray(n, jnp.float32)) * 1e-6 * jnp.linalg.norm(y)
    y = y + nu * omega.astype(jnp.float32)
    g = _dot(omega.astype(jnp.float32).T, y)
    g = 0.5 * (g + g.T)                                   # symmetrize
    c = jnp.linalg.cholesky(g)
    b = jax.scipy.linalg.solve_triangular(c, y.T, lower=True).T
    u, sig, _ = jnp.linalg.svd(b, full_matrices=False)
    lam = jnp.maximum(sig**2 - nu, 0.0)
    return u[:, :rank], lam[:rank]


# ---------------------------------------------------------------------------
# Test-matrix generators (paper §5.1.1 and §3.3)
# ---------------------------------------------------------------------------

def matrix_with_singular_values(key: jax.Array, n: int, s_vals: jax.Array) -> jax.Array:
    """Random n x n matrix with prescribed singular values (slatms role):
    U diag(s) V^T with Haar-ish U, V from QR of Gaussians."""
    k1, k2 = jax.random.split(key)
    u, _ = jnp.linalg.qr(jax.random.normal(k1, (n, n), dtype=jnp.float32))
    v, _ = jnp.linalg.qr(jax.random.normal(k2, (n, n), dtype=jnp.float32))
    return _dot(u * s_vals[None, :], v.T)


def singular_values_linear(n: int, p: int, s_p: float) -> jax.Array:
    """A_linear spectrum: s_i = max(-alpha_l * i + 1, s_p), alpha_l=(1-s_p)/p."""
    i = jnp.arange(n, dtype=jnp.float32)
    alpha = (1.0 - s_p) / p
    return jnp.maximum(-alpha * i + 1.0, s_p)


def singular_values_exp(n: int, p: int, s_p: float) -> jax.Array:
    """A_exp spectrum: s_i = 2^(-alpha_e * i), alpha_e = log2(1/s_p)/p."""
    i = jnp.arange(n, dtype=jnp.float32)
    alpha = jnp.log2(1.0 / s_p) / p
    return jnp.exp2(-alpha * i)


def matrix_type1(key: jax.Array, n: int = 4096, r: int = 20,
                 xi: float = 1e-4) -> jax.Array:
    """§3.3 Type 1: D + xi * G G^T with D = diag(I_r, 0)."""
    g = jax.random.normal(key, (n, n), dtype=jnp.float32)
    d = jnp.diag(jnp.concatenate([jnp.ones(r), jnp.zeros(n - r)]).astype(jnp.float32))
    return d + xi * _dot(g, g.T) / n  # /n keeps the noise term O(xi)


def matrix_type2(key: jax.Array, n: int = 4096, r: int = 20, alpha: float = 3.0,
                 phi: float = 1e6) -> jax.Array:
    """§3.3 Type 2 (= A_poly): U diag(phi*I_r, 2^-a, 3^-a, ...) V^T, Haar U,V."""
    head = jnp.full((r,), phi, dtype=jnp.float32)
    tail = jnp.arange(2, n - r + 2, dtype=jnp.float32) ** (-alpha)
    return matrix_with_singular_values(key, n, jnp.concatenate([head, tail]))


def matrix_cauchy(key: jax.Array, n: int = 4096, gamma: float = 1e-3) -> jax.Array:
    """§5.1.1 Cauchy matrix: 1/(|x_i - y_j| + gamma), x,y ~ U(-1e-3, 1e-3).

    Elements reach ~1/gamma = 1000 > fp16's safe range after accumulation; on
    the paper's fp16 path this overflows — on our bf16 path it does not
    (hardware-adaptation win, DESIGN.md §2).
    """
    kx, ky = jax.random.split(key)
    x = jax.random.uniform(kx, (n, 1), minval=-1e-3, maxval=1e-3)
    y = jax.random.uniform(ky, (1, n), minval=-1e-3, maxval=1e-3)
    return (1.0 / (jnp.abs(x - y) + gamma)).astype(jnp.float32)
