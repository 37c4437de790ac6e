"""Distributed RandNLA: sharded mixed-precision projection, TSQR, RSVD.

Designed for the production mesh (data, model) [optionally (pod, data, model)]:

  * A is sharded rows->data(+pod), cols->model (2-D block layout).
  * Projection Y = A . Omega: Omega row-sharded over model; each shard runs
    the LOCAL mixed-precision SHGEMM (the paper's kernel), then one
    reduce-scatter/psum over `model` — SUMMA with a single panel, because the
    sketch width p_hat is small.
  * QR of the tall-skinny Y via TSQR over the data axis: local QR -> gather
    the tiny R factors -> QR of the stacked R -> local Q update.  Collective
    volume is O(dp * p_hat^2), independent of m.
  * B = Q^T A: local GEMM + psum over data; tSVD of B via a second TSQR of
    B^T across the model axis (no Gram squaring — matches single-device
    accuracy; only p_hat^2 factors are ever replicated).

Everything is shard_map'd, so the same code lowers on the 512-device
production mesh in the dry-run and runs on small host meshes in tests.
"""

from __future__ import annotations

import functools
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.projection import ProjectionMethod, gaussian, project


class ShardedSVD(NamedTuple):
    u: jax.Array    # (m, rank) rows sharded over data
    s: jax.Array    # (rank,) replicated
    vt: jax.Array   # (rank, n) cols sharded over model


def _local_project(a_blk, om_blk, method: ProjectionMethod, model_axis: str):
    """Per-shard projection + reduction over the model (column) axis."""
    y = project(a_blk, om_blk, method=method)
    return jax.lax.psum(y, model_axis)


def _local_sketch_fused(a_blk, key2, p_hat: int, model_axis: str,
                        omega_dtype=jnp.bfloat16):
    """Per-shard fused projection: this device's Omega row-block is generated
    **in-kernel** from (key, global column offset) — zero HBM bytes and zero
    collectives for the random matrix (DESIGN.md §9/§10).  The generated
    block is bit-identical to ``fused_omega(key, (n, p_hat))[off:off+n_loc]``
    (the counter hash depends only on global indices), so the shard-local
    GEMM matches the materialized-slice path bit for bit.
    """
    from repro.kernels import ops  # deferred: keeps core import-light
    n_loc = a_blk.shape[1]
    off = jax.lax.axis_index(model_axis) * n_loc
    y = ops.shgemm_fused(a_blk.astype(jnp.float32), key2, p_hat,
                         omega_dtype=omega_dtype, row_offset=off)
    return jax.lax.psum(y, model_axis)


def _dot(a, b):
    """f32 GEMM at full precision: a TPU's default f32 dot is one bf16
    pass, which put distributed rSVD at 16x the single-device residual."""
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _tsqr(y_blk: jax.Array, data_axis: str) -> tuple[jax.Array, jax.Array]:
    """Tall-skinny QR across the data axis.  y_blk: (m_local, p)."""
    p = y_blk.shape[1]
    q1, r1 = jnp.linalg.qr(y_blk)                      # local QR
    r_all = jax.lax.all_gather(r1, data_axis)          # (dp, p, p) — tiny
    q2, r = jnp.linalg.qr(r_all.reshape(-1, p))        # (dp*p, p) QR
    idx = jax.lax.axis_index(data_axis)
    q2_blk = jax.lax.dynamic_slice_in_dim(q2, idx * p, p, axis=0)
    return _dot(q1, q2_blk), r


def distributed_range_finder(key, a: jax.Array, p_hat: int, mesh: Mesh, *,
                             method: ProjectionMethod = "shgemm",
                             omega_dtype=jnp.bfloat16,
                             data_axis: str = "data",
                             model_axis: str = "model") -> jax.Array:
    """Q (m, p_hat), rows sharded over data, s.t. A ~ Q Q^T A.

    With ``method="shgemm_fused"`` no Omega is materialized anywhere: each
    device hashes exactly its row-block out of the counter stream inside the
    kernel (``_local_sketch_fused``).  Other methods keep the legacy
    host-materialized jax.random Omega bit for bit.
    """
    from repro.kernels import shgemm_fused as _kf

    if method == "shgemm_fused":
        def fn_fused(a_blk, key2):
            y = _local_sketch_fused(a_blk, key2, p_hat, model_axis,
                                    omega_dtype=omega_dtype)
            q, _ = _tsqr(y, data_axis)
            return q

        return jax.shard_map(
            fn_fused, mesh=mesh,
            in_specs=(P(data_axis, model_axis), P(None, None)),
            out_specs=P(data_axis, None), check_vma=False,
        )(a, _kf.key_words(key))

    n = a.shape[1]
    omega = gaussian(key, (n, p_hat), dtype=omega_dtype)

    def fn(a_blk, om_blk):
        y = _local_project(a_blk, om_blk, method, model_axis)
        q, _ = _tsqr(y, data_axis)
        return q

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(data_axis, model_axis), P(model_axis, None)),
        out_specs=P(data_axis, None), check_vma=False,
    )(a, omega)


@functools.partial(jax.jit, static_argnames=("rank", "oversample", "method",
                                             "power_iters", "mesh",
                                             "data_axis", "model_axis"))
def distributed_rsvd(key, a: jax.Array, rank: int, mesh: Mesh, *,
                     oversample: int = 10, power_iters: int = 0,
                     method: ProjectionMethod = "shgemm",
                     data_axis: str = "data",
                     model_axis: str = "model") -> ShardedSVD:
    """Randomized SVD of a 2-D-sharded A; never materializes anything bigger
    than (m_local x n_local) per device or p_hat^2 replicated.

    power_iters: q passes of the (A A^T)^q power scheme (paper §2.1) — each
    pass is two sharded GEMMs + a TSQR re-orthogonalization.

    ``method="shgemm_fused"`` generates each shard's Omega row-block
    in-kernel from (key, global offset) — nothing is materialized, sharded,
    or communicated for the random matrix; all other methods keep the
    legacy materialized Omega path unchanged."""
    from repro.kernels import shgemm_fused as _kf

    m, n = a.shape
    p_hat = min(rank + oversample, min(m, n))
    fused = method == "shgemm_fused"
    if fused:
        aux = _kf.key_words(key)                       # (1, 2) replicated
        aux_spec = P(None, None)
    else:
        aux = gaussian(key, (n, p_hat), dtype=jnp.bfloat16)
        aux_spec = P(model_axis, None)

    def fn(a_blk, aux_blk):
        # Lines 1-2: projection + TSQR over data.
        if fused:
            y = _local_sketch_fused(a_blk, aux_blk, p_hat, model_axis)
        else:
            y = _local_project(a_blk, aux_blk, method, model_axis)
        q, _ = _tsqr(y, data_axis)                     # (m_loc, p_hat)
        for _ in range(power_iters):
            # z = A^T q : (n_loc, p_hat), psum over data
            z = jax.lax.psum(_dot(a_blk.T, q), data_axis)
            z, _ = _tsqr(z, model_axis)
            # y = A z : (m_loc, p_hat), psum over model
            y = jax.lax.psum(_dot(a_blk, z), model_axis)
            q, _ = _tsqr(y, data_axis)
        # Line 3: B = Q^T A, cols sharded over model.
        b_blk = jax.lax.psum(_dot(q.T, a_blk), data_axis)
        # Line 4 WITHOUT Gram squaring (would double the condition number):
        # TSQR of B^T across model -> B = R^T Q_bt^T; small SVD of R^T.
        q_bt, r_bt = _tsqr(b_blk.T, model_axis)        # (n_loc, p), (p, p)
        u_b, s, wt = jnp.linalg.svd(r_bt.T, full_matrices=False)
        vt_blk = _dot(wt, q_bt.T)                      # (p, n_loc) sharded
        u = _dot(q, u_b)
        return u[:, :rank], s[:rank], vt_blk[:rank, :]

    u, s, vt = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(data_axis, model_axis), aux_spec),
        out_specs=(P(data_axis, None), P(), P(None, model_axis)),
        check_vma=False,
    )(a, aux)
    return ShardedSVD(u, s, vt)


def shard_matrix(a: jax.Array, mesh: Mesh, data_axis="data", model_axis="model"):
    """Place an (m, n) matrix with the library's canonical 2-D layout."""
    return jax.device_put(a, NamedSharding(mesh, P(data_axis, model_axis)))


def _shard_map_stack(fn, items, mesh: Mesh, axis: str):
    """Run a collective ``fn`` over per-shard pytrees: stack ``items`` on a
    new leading axis (one slice per shard of ``axis``), shard_map ``fn``
    over each shard's squeezed slice, return the replicated result.  The
    single home of the stack/in_specs/squeeze plumbing — every
    simulated-hosts dispatch (psum partials, sketch merge, tests) goes
    through here."""
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *items)

    def body(item):
        return fn(jax.tree.map(lambda x: jnp.squeeze(x, 0), item))

    return jax.shard_map(body, mesh=mesh, in_specs=(P(axis),),
                            out_specs=P(), check_vma=False)(stacked)


def _psum_stack(parts, mesh: Mesh, axis: str):
    """Replicated sum of per-host partials with a single mesh psum."""
    return _shard_map_stack(lambda x: jax.lax.psum(x, axis), parts, mesh,
                            axis)


def _dist_payload(resil, done, cur, host):
    """Checkpoint payload for the distributed sketch pass: the fold-merge
    of all completed hosts (``done``), the in-flight host's partial
    (``cur``), and which host the cursor is in."""
    arrays, meta = {}, {}
    if done is not None:
        arrays, meta = resil.state_to_payload(done, prefix="done")
    if cur is not None:
        a2, m2 = resil.state_to_payload(cur, prefix="cur")
        arrays.update(a2)
        meta.update(m2)
    meta["cursor"] = {"host": int(host)}
    return arrays, meta


def distributed_rsvd_streamed(key, sources, rank: int, mesh: Mesh, *,
                              oversample: int = 10, passes: int = 2,
                              method: ProjectionMethod = "shgemm_fused",
                              omega_dtype=jnp.bfloat16,
                              data_axis: str = "data",
                              prefetch_depth: int | None = 1,
                              checkpoint_dir=None,
                              checkpoint_every_tiles: int | None = None,
                              resume: bool = False,
                              return_report: bool = False):
    """Multi-host × out-of-core randomized SVD: every shard of the data
    axis streams its own :class:`~repro.stream.TileSource` (a disjoint
    global row range of A, e.g. one ``.npy`` shard dir per host), the
    per-host sketches combine with ``stream.merge_across_hosts`` — one
    psum, exact bit-for-bit for disjoint rows — and every later pass
    accumulates per-host partials joined by one psum each.

    ``sources`` — one tile source per shard of ``data_axis``, in global row
    order (source i covers rows ``[sum_{j<i} rows_j, ...)``); each must be
    replayable for ``passes >= 2`` and may use a different tiling.  This
    single-controller driver loops over all sources itself (simulated
    hosts); a true multi-process deployment runs the identical per-host
    loop on its local source only — the collective algebra is the same.
    With ``method="shgemm_fused"`` every host hashes its tiles' Omega
    row-blocks in-kernel from (key, global offset): nothing is ever
    materialized, stored, or communicated for the random matrix, and the
    merged sketch is bit-identical to single-host ``rsvd_streamed`` of the
    concatenated source.  ``passes`` semantics match ``rsvd_streamed``
    (>= 2; streamed power iteration beyond 2).

    Returns a replicated ``core.rsvd.SVDResult``.  A itself never
    materializes anywhere; each host's sketch/basis state is O(m·p_hat)
    (global rows) plus one tile of A and p_hat·n factors.  NB: this
    single-controller simulation additionally holds all ``len(sources)``
    per-host states (and one stacked copy) at once — a
    ``len(sources)``-times multiplier a true multi-process deployment,
    which holds only its own state, does not pay.

    Fault tolerance (``checkpoint_dir=...``, DESIGN.md §14): pass 1
    checkpoints at tile granularity — the payload is the fold-merge of
    all fully-sketched hosts plus the in-flight host's partial state and
    cursor (fold-merging disjoint-row states is bitwise equal to the
    collective psum, so the checkpointed path returns the identical
    factors).  Later passes checkpoint at pass boundaries via the shared
    power-iteration driver, so a kill there replays at most one pass.
    ``resume=True`` restarts from the last checkpoint;
    ``return_report=True`` appends a
    :class:`repro.stream.resilience.ResilienceReport`.
    """
    from repro import stream  # deferred: stream imports core modules
    from repro.core.rsvd import _dot, streamed_power_factor

    if passes < 2:
        raise ValueError("distributed_rsvd_streamed needs passes >= 2; the "
                         "strict single-pass finalizer is single-host "
                         "(stream.svd) — merge left-sketch states with "
                         "merge_across_hosts directly instead")
    srcs = [stream.as_tile_source(s) for s in sources]
    if data_axis not in mesh.shape or mesh.shape[data_axis] != len(srcs):
        raise ValueError(f"{len(srcs)} tile sources need a {data_axis!r} "
                         f"mesh axis of size {len(srcs)}, got mesh "
                         f"{dict(mesh.shape)}")
    bad = [i for i, s in enumerate(srcs) if not s.replayable]
    if bad:
        raise ValueError(f"passes={passes} must replay every tile stream; "
                         f"sources {bad} are not replayable")
    n_cols = srcs[0].n_cols
    for i, s in enumerate(srcs):
        if s.n_cols != n_cols:
            raise ValueError(f"source {i} has {s.n_cols} columns, "
                             f"source 0 has {n_cols}")
    row_starts = []
    m = 0
    for s in srcs:
        row_starts.append(m)
        m += s.n_rows
    p_hat = min(rank + oversample, min(m, n_cols))

    ck = None
    restored = None
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
        fingerprint = {
            "job": "distributed_rsvd_streamed",
            "key": resil.key_fingerprint(key),
            "rank": int(rank), "p_hat": int(p_hat), "passes": int(passes),
            "method": str(method),
            "omega_dtype": str(jnp.dtype(omega_dtype)),
            "n_rows": int(m), "n_cols": int(n_cols),
            "hosts": len(srcs),
        }
        ck = resil.SketchJobCheckpointer(
            checkpoint_dir,
            every_tiles=(16 if checkpoint_every_tiles is None
                         else checkpoint_every_tiles),
            fingerprint=fingerprint, resume=resume)
        restored = ck.restore()

    def host_tiles(s, r0, start_local=0):
        off = r0 + start_local
        it = stream.source_tiles(s, prefetch_depth=prefetch_depth,
                                 start_row=start_local)
        if ck is not None:
            it = ck.guard(it)
        t_last = time.perf_counter()
        for blk in it:
            yield off, blk
            off += blk.shape[0]
            if ck is not None:
                now = time.perf_counter()
                ck.note_tile(now - t_last)
                t_last = now
        if off - r0 != s.n_rows:
            raise ValueError(f"source tiles cover {off - r0} rows, its "
                             f"shape promises {s.n_rows}")

    def finished(res):
        if ck is None:
            return res
        report = ck.finish(tiles_total=sum(
            resil._count_tiles(s) or 0 for s in srcs) * passes)
        return (res, report) if return_report else res

    power_resume = None
    if restored is not None and restored.phase == "power":
        power_resume = restored
    elif restored is not None and restored.phase != "dist-sketch":
        raise RuntimeError(f"checkpoint under {checkpoint_dir} is in "
                           f"unknown phase {restored.phase!r}")

    merged = None
    if power_resume is None and ck is None:
        # Pass 1: per-host sketches over the GLOBAL Omega lattice, then the
        # collective merge.  Disjoint row coverage makes the psum exact.
        states = []
        for s, r0 in zip(srcs, row_starts):
            st = stream.init(key, n_cols, p_hat, max_rows=m, method=method,
                             omega_dtype=omega_dtype)
            for off, blk in host_tiles(s, r0):
                st = stream.update(st, blk, off)
            states.append(st)
        merged = _shard_map_stack(
            lambda st: stream.merge_across_hosts(st, data_axis),
            states, mesh, data_axis)
    elif power_resume is None:
        # Checkpointed pass 1: fold-merge each finished host into `done`
        # (bitwise equal to the psum — disjoint rows), checkpoint
        # done + in-flight partial + cursor at tile granularity.
        done = None
        h_start, local_start, g_tiles = 0, 0, 0
        cur0 = None
        if restored is not None:
            if "done.y" in restored.arrays:
                done = resil.state_from_payload(restored.arrays,
                                                restored.meta, "done")
            if "cur.y" in restored.arrays:
                cur0 = resil.state_from_payload(restored.arrays,
                                                restored.meta, "cur")
            h_start = int(restored.meta["cursor"]["host"])
            g_tiles = restored.tiles_done
            if h_start < len(srcs):
                local_start = restored.rows_done - row_starts[h_start]
        for h in range(h_start, len(srcs)):
            s, r0 = srcs[h], row_starts[h]
            if h == h_start and cur0 is not None:
                st, start_local = cur0, local_start
            else:
                st = stream.init(key, n_cols, p_hat, max_rows=m,
                                 method=method, omega_dtype=omega_dtype)
                start_local = 0
            for off, blk in host_tiles(s, r0, start_local):
                st = stream.update(st, blk, off)
                g_tiles += 1
                ck.tick(phase="dist-sketch", pass_idx=1,
                        tiles_done=g_tiles,
                        rows_done=int(off + blk.shape[0]),
                        payload=lambda d=done, c=st, hh=h:
                            _dist_payload(resil, d, c, hh))
            done = st if done is None else stream.merge(done, st)
        merged = done
        ck.commit(phase="dist-sketch", pass_idx=1, tiles_done=g_tiles,
                  rows_done=int(m),
                  payload=lambda: _dist_payload(resil, merged, None,
                                                len(srcs)))

    # Passes 2..: the shared power-iteration driver (rsvd.py owns the
    # algebra — single-host and distributed cannot drift), with each
    # accumulation built per host and joined by one psum.
    def accumulate_b(q):
        parts = []
        for s, r0 in zip(srcs, row_starts):
            b_h = jnp.zeros((p_hat, n_cols), jnp.float32)
            for off, blk in host_tiles(s, r0):
                b_h = b_h + _dot(q[off:off + blk.shape[0]].T,
                                 jnp.asarray(blk, jnp.float32))
            parts.append(b_h)
        return _psum_stack(parts, mesh, data_axis)     # B = Q^T A

    def accumulate_y(z):
        # each host's tiles cover [r0, r0 + rows) in order: concatenate the
        # per-tile products between zero pads (O(m·p) per host, no
        # per-tile full-buffer copies); the psum of disjoint rows is exact
        parts = []
        for s, r0 in zip(srcs, row_starts):
            segs = [_dot(jnp.asarray(blk, jnp.float32), z)
                    for _, blk in host_tiles(s, r0)]
            parts.append(jnp.concatenate(
                [jnp.zeros((r0, p_hat), jnp.float32), *segs,
                 jnp.zeros((m - r0 - s.n_rows, p_hat), jnp.float32)],
                axis=0))
        return _psum_stack(parts, mesh, data_axis)     # Y = A Z (rows exact)

    on_pass_done = None
    if ck is not None:
        def on_pass_done(pass_idx, which, basis):
            ck.commit(phase="power", pass_idx=pass_idx, tiles_done=0,
                      rows_done=0,
                      payload=lambda: ({"basis": np.asarray(basis)},
                                       {"power": {"which": which}}))

    if power_resume is not None:
        basis = jnp.asarray(power_resume.arrays["basis"])
        which = power_resume.meta["power"]["which"]
        return finished(streamed_power_factor(
            basis if which == "q" else None, rank, passes,
            accumulate_b=accumulate_b, accumulate_y=accumulate_y,
            start_pass=power_resume.pass_idx + 1,
            z=basis if which == "z" else None,
            start_on_rows=(which == "q"), on_pass_done=on_pass_done))

    return finished(streamed_power_factor(
        stream.range_basis(merged), rank, passes,
        accumulate_b=accumulate_b, accumulate_y=accumulate_y,
        on_pass_done=on_pass_done))
