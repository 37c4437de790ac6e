"""Random-projection HOSVD (paper Algorithm 2) + tensor utilities.

RP-HOSVD factorizes A in R^{I1 x ... x IN} as a core tensor g contracted with
orthonormal factor matrices Q_k, using a random projection + QR per mode
instead of a full SVD of each unfolding.  The mode-k projection
W = A'_(k) . Omega_(k) is the O(prod(I) * J_k) hot spot and runs through the
paper's mixed-precision SHGEMM.
"""

from __future__ import annotations

import functools
import time
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import projection as proj


class TuckerResult(NamedTuple):
    core: jax.Array                 # (J1, ..., JN)
    factors: tuple[jax.Array, ...]  # Q_k: (I_k, J_k)


def unfold(t: jax.Array, mode: int) -> jax.Array:
    """Mode-k unfolding: (I_k, prod_{j!=k} I_j)."""
    perm = (mode,) + tuple(i for i in range(t.ndim) if i != mode)
    return jnp.transpose(t, perm).reshape(t.shape[mode], -1)


def fold(m: jax.Array, mode: int, shape: Sequence[int]) -> jax.Array:
    """Inverse of unfold."""
    full = (shape[mode],) + tuple(s for i, s in enumerate(shape) if i != mode)
    t = m.reshape(full)
    inv = list(range(1, mode + 1)) + [0] + list(range(mode + 1, len(shape)))
    return jnp.transpose(t, inv)


def mode_dot(t: jax.Array, m: jax.Array, mode: int) -> jax.Array:
    """Contraction T x_k M with M: (J, I_k) applied as M . T_(k)."""
    unf = unfold(t, mode)
    res = jnp.dot(m, unf, precision=jax.lax.Precision.HIGHEST,
                  preferred_element_type=jnp.float32)
    new_shape = list(t.shape)
    new_shape[mode] = m.shape[0]
    return fold(res, mode, new_shape)


def _mode_sketch(key: jax.Array, core: jax.Array, i: int, rank: int, *,
                 method, dist, omega_dtype) -> jax.Array:
    """W = A_(i) · Omega_i for one mode — the per-mode hot GEMM, or the
    Khatri–Rao factor-by-factor contraction that replaces it.

    ``dist="khatri_rao"`` (Tensorized Random Projections, arXiv 2003.05101)
    never forms the (I_i, prod I_k) unfolding OR the (prod I_k, J_i) Omega:
    the tensor is contracted against small per-mode factors, so no
    intermediate carries the unfolding's column dimension."""
    if dist == "khatri_rao":
        from repro.core import structured as _sx
        kro = _sx.KhatriRaoOmega(key=key, dims=tuple(core.shape), mode=i,
                                 p=rank)
        return kro.sketch_slab(core)
    unf = unfold(core, i)                        # (I_i, prod I_k)
    return proj.sketch(key, unf, rank, method=method, dist=dist,
                       omega_dtype=omega_dtype)


@functools.partial(jax.jit, static_argnames=("ranks", "method", "dist",
                                             "omega_dtype"))
def rp_hosvd(key: jax.Array, a: jax.Array, ranks: tuple[int, ...], *,
             method: proj.ProjectionMethod = "shgemm",
             dist: proj.SketchDist = "gaussian",
             omega_dtype=jnp.bfloat16) -> TuckerResult:
    """Paper Algorithm 2.

    For each mode i: W = A_(i) . Omega_i with Omega_i (prod_{k!=i} I_k, J_i)
    in low precision; Q_i <- QR(W).  Core: g = A x_1 Q_1^T ... x_N Q_N^T.
    """
    a = a.astype(jnp.float32)
    keys = jax.random.split(key, a.ndim)
    factors = []
    for i in range(a.ndim):
        # line 2 — the hot GEMM; key-based so method="shgemm_fused" streams
        # Omega_(i) out of the hash instead of HBM (it is the *largest*
        # operand here: prod_{k!=i} I_k rows), and dist="khatri_rao" skips
        # the unfolding-width contraction entirely (_mode_sketch).
        with jax.named_scope("hosvd.project"):
            w = _mode_sketch(keys[i], a, i, ranks[i], method=method,
                             dist=dist, omega_dtype=omega_dtype)
        with jax.named_scope("hosvd.factor"):
            q, _ = jnp.linalg.qr(w)              # line 3
        factors.append(q)
    core = a
    for i, q in enumerate(factors):
        with jax.named_scope("hosvd.core"):
            core = mode_dot(core, q.T, i)        # line 5
    return TuckerResult(core, tuple(factors))


@functools.partial(jax.jit, static_argnames=("ranks", "method", "dist",
                                             "omega_dtype"))
def rp_sthosvd(key: jax.Array, a: jax.Array, ranks: tuple[int, ...], *,
               method: proj.ProjectionMethod = "shgemm",
               dist: proj.SketchDist = "gaussian",
               omega_dtype=jnp.bfloat16) -> TuckerResult:
    """Sequentially-truncated variant (beyond-paper: each mode's projection
    operates on the already-compressed tensor, cutting the later GEMMs)."""
    core = a.astype(jnp.float32)
    keys = jax.random.split(key, a.ndim)
    factors = []
    for i in range(a.ndim):
        with jax.named_scope("hosvd.project"):
            w = _mode_sketch(keys[i], core, i, ranks[i], method=method,
                             dist=dist, omega_dtype=omega_dtype)
        with jax.named_scope("hosvd.factor"):
            q, _ = jnp.linalg.qr(w)
        factors.append(q)
        with jax.named_scope("hosvd.core"):
            core = mode_dot(core, q.T, i)
    return TuckerResult(core, tuple(factors))


def rp_sthosvd_streamed(key: jax.Array, slabs, dims=None, ranks=None, *,
                        method: proj.ProjectionMethod = "shgemm_fused",
                        dist: proj.SketchDist = "gaussian",
                        omega_dtype=jnp.bfloat16,
                        prefetch_depth: int | None = 1,
                        tol: float | None = None,
                        max_ranks=None,
                        checkpoint_dir=None,
                        checkpoint_every_tiles: int | None = None,
                        resume: bool = False,
                        return_report: bool = False) -> TuckerResult:
    """Single-pass streaming Tucker of a tensor that arrives as slabs along
    axis 0 (out-of-core tensors, token/frame streams).

    ``slabs`` is anything ``stream.as_tile_source`` accepts — a
    ``TileSource`` (memmapped ``.npy``, directory of shards, object-store
    shards behind range reads, in-memory array) or a plain iterable of
    ``A[off:off+b, ...]`` slabs in order, tiling axis 0 exactly.  ``dims``
    (the full tensor shape) may be omitted
    when the source knows it; slabs are double-buffer prefetched
    (DESIGN.md §11, ``prefetch_depth=None`` disables).  Never holds more
    than ``prefetch_depth + 1`` slabs plus the O(sum_i I_i·J_i) sketch
    state — the per-mode Omega_i (whose row count is prod_{j!=i} I_j, the
    *largest* object in one-shot RP-HOSVD) is regenerated block-wise
    in-kernel and never materialized (repro.stream.tucker).

    Per-mode adaptive ranks (``tol=..., max_ranks=...``, DESIGN.md §13):
    instead of fixed ``ranks``, sketch once at the per-mode ceilings
    ``max_ranks`` and let :func:`truncate_tucker` pick each mode's rank at
    finalize — the smallest per-mode ranks whose combined discarded tail
    keeps the estimated relative error under ``tol``.  Still a single
    pass: the rank decision needs only the (tiny) core, so "grow between
    passes" (the rSVD adaptive driver's replay loop) is unnecessary here —
    the ceilings bound the work and the truncation reveals the rank.

    Fault tolerance (``checkpoint_dir=...``, DESIGN.md §14): the whole
    job is one slab pass over a TuckerSketch, checkpointed with its slab
    cursor every ``checkpoint_every_tiles`` slabs; ``resume=True``
    restarts from the last checkpoint and the result is bitwise equal to
    the uninterrupted run (slab updates write disjoint core/mode-sketch
    slices; replay preserves slab order).  Adaptive ``tol=`` composes
    freely here — the sketch widths are fixed at init, the rank decision
    happens after the stream.  ``return_report=True`` returns
    ``(TuckerResult, ResilienceReport)``.
    """
    from repro import stream  # deferred: stream imports this module
    if tol is not None:
        if ranks is not None:
            raise ValueError("pass either fixed ranks= or adaptive "
                             "tol=+max_ranks=, not both")
        if max_ranks is None:
            raise ValueError("adaptive mode (tol=) needs max_ranks= — the "
                             "per-mode sketch widths / rank ceilings")
        if float(tol) <= 0.0:
            raise ValueError(f"tol must be > 0, got {tol}")
        ranks = tuple(int(r) for r in max_ranks)
    elif max_ranks is not None:
        raise ValueError("max_ranks only applies to adaptive (tol=...) "
                         "runs")
    if ranks is None:
        raise TypeError("rp_sthosvd_streamed missing required ranks")
    try:
        src = stream.as_tile_source(
            slabs, shape=tuple(int(d) for d in dims) if dims is not None
            else None)
    except ValueError as e:
        if dims is None and "shape" in str(e):
            raise ValueError(
                "this slab stream cannot be inspected for its shape: pass "
                "dims= (or stream from a TileSource/array/.npy path, "
                "which knows its shape)") from e
        raise
    if dims is not None and tuple(int(d) for d in dims) != src.shape:
        raise ValueError(f"dims={tuple(dims)} but the slab source has "
                         f"shape {src.shape}")
    dims = src.shape

    ck = None
    if checkpoint_dir is None:
        if checkpoint_every_tiles is not None:
            raise ValueError("checkpoint_every_tiles needs checkpoint_dir=")
        if resume:
            raise ValueError("resume=True needs checkpoint_dir= (there is "
                             "nowhere to resume from)")
        if return_report:
            raise ValueError("return_report=True needs checkpoint_dir= "
                             "(the report measures the checkpointed job)")
    else:
        from repro.stream import resilience as resil
        if not src.replayable:
            raise ValueError(
                "checkpoint_dir needs a replayable slab source: resuming "
                "replays the slab suffix after the checkpointed cursor, "
                "which a one-shot generator cannot provide")
        fingerprint = {
            "job": "rp_sthosvd_streamed",
            "key": resil.key_fingerprint(key),
            "dims": [int(d) for d in dims],
            "ranks": [int(r) for r in ranks],
            "method": str(method), "dist": str(dist),
            "omega_dtype": str(jnp.dtype(omega_dtype)),
        }
        ck = resil.SketchJobCheckpointer(
            checkpoint_dir,
            every_tiles=(16 if checkpoint_every_tiles is None
                         else checkpoint_every_tiles),
            fingerprint=fingerprint, resume=resume)

    start_tile = start_row = 0
    restored = ck.restore() if ck is not None else None
    if restored is not None:
        if restored.phase != "tucker":
            raise RuntimeError(f"checkpoint under {checkpoint_dir} is in "
                               f"unknown phase {restored.phase!r}")
        ts = resil.tucker_from_payload(restored.arrays, restored.meta)
        start_tile, start_row = restored.tiles_done, restored.rows_done
    else:
        ts = stream.tucker_init(key, dims, ranks, method=method, dist=dist,
                                omega_dtype=omega_dtype)

    off = start_row
    tiles_done = start_tile
    it = stream.source_tiles(src, prefetch_depth=prefetch_depth,
                             start_row=start_row)
    if ck is not None:
        it = ck.guard(it)
    t_last = time.perf_counter()
    for slab in it:
        ts = stream.tucker_update(ts, slab, off)
        off += slab.shape[0]
        tiles_done += 1
        if ck is not None:
            now = time.perf_counter()
            ck.note_tile(now - t_last)
            t_last = now
            ck.tick(phase="tucker", pass_idx=1, tiles_done=tiles_done,
                    rows_done=int(off),
                    payload=lambda t=ts: resil.tucker_to_payload(t))
    if off != dims[0]:
        raise ValueError(f"slabs cover {off} rows of axis 0, expected "
                         f"{dims[0]}")
    res = stream.tucker_finalize(ts)
    if tol is not None:
        res = truncate_tucker(res, tol)
    if ck is not None:
        # final commit so a crash AFTER the stream (during finalize) still
        # resumes with zero slab recomputation
        ck.commit(phase="tucker", pass_idx=1, tiles_done=tiles_done,
                  rows_done=int(off),
                  payload=lambda: resil.tucker_to_payload(ts))
        report = ck.finish(tiles_total=resil._count_tiles(src) or tiles_done)
        if return_report:
            return res, report
    return res


def truncate_tucker(res: TuckerResult, tol: float, *,
                    min_rank: int = 1) -> TuckerResult:
    """Per-mode adaptive rank truncation — the rank-revealing stopping
    rule for Tucker factorizations (DESIGN.md §13).

    Rotates each mode into the core's singular basis and keeps the
    smallest rank whose discarded spectral tail fits that mode's share of
    the error budget (the ST-HOSVD split: per-mode tail² <=
    tol²·||core||²/N, so the N truncations together keep the total
    relative error of the *captured* tensor under ``tol``).  ``tol`` is
    relative to ||core||_F ≈ ||A||_F — an estimate, not a certificate:
    whatever the fixed-ceiling sketch already lost is not counted
    (rsvd_streamed's tol= driver is the certified path for matrices).
    Runs eagerly (data-dependent output shapes cannot live under jit).
    """
    if tol <= 0.0:
        raise ValueError(f"tol must be > 0, got {tol}")
    core = jnp.asarray(res.core, jnp.float32)
    factors = list(res.factors)
    ndim = core.ndim
    total2 = float(jnp.sum(core * core))
    budget2 = (float(tol) ** 2) * total2 / ndim
    for i in range(ndim):
        u, s, _ = jnp.linalg.svd(unfold(core, i), full_matrices=False)
        s2 = np.asarray(s, np.float64) ** 2
        revcum = np.cumsum(s2[::-1])[::-1]  # revcum[r] = sum_{j>=r} s2[j]
        keep = len(s2)
        for r in range(max(1, int(min_rank)), len(s2)):
            if revcum[r] <= budget2:
                keep = r
                break
        factors[i] = jnp.dot(factors[i], u[:, :keep],
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
        core = mode_dot(core, u[:, :keep].T, i)
    return TuckerResult(core, tuple(factors))


def reconstruct(res: TuckerResult) -> jax.Array:
    t = res.core
    for i, q in enumerate(res.factors):
        t = mode_dot(t, q, i)
    return t


def reconstruction_error(a: jax.Array, res: TuckerResult) -> jax.Array:
    a = a.astype(jnp.float32)
    return jnp.linalg.norm(a - reconstruct(res)) / jnp.linalg.norm(a)


def make_test_tensor(key: jax.Array, dims: Sequence[int], ranks: Sequence[int],
                     pad: int = 2) -> jax.Array:
    """Paper Algorithm 3: low-multilinear-rank test tensor.

    G ~ U(-1,1)^{J1 x ... x JN}; per mode contract with a (J_i - pad)-rank
    matrix Omega_a . Omega_b mapping J_i -> I_i.
    """
    keys = jax.random.split(key, 2 * len(dims) + 1)
    g = jax.random.uniform(keys[0], tuple(ranks), minval=-1.0, maxval=1.0)
    for i, (ii, ji) in enumerate(zip(dims, ranks)):
        oa = jax.random.uniform(keys[2 * i + 1], (ji - pad, ji), minval=-1, maxval=1)
        ob = jax.random.uniform(keys[2 * i + 2], (ii, ji - pad), minval=-1, maxval=1)
        g = mode_dot(g, jnp.dot(ob, oa), i)  # (J_i - pad)-rank map J_i -> I_i
    return g
