"""Fault-tolerant resumable sketch jobs (stream/resilience.py, DESIGN.md §14).

Pins the resilience contract end to end: checkpoint/restore round-trips
bitwise for every projection method and every phase (sketch / B / power /
tucker / distributed), a SIGKILLed job resumed from disk reproduces the
uninterrupted factors bit for bit with bounded recomputation (the
subprocess kill-and-resume test — a real preemption, not a simulated
exception), injected faults behave as configured (FaultySource
raise/hang/kill, FlakyRangeFetcher timeouts/5xx/truncation), transient
fetch errors retry with backoff while permanent errors fail loudly on the
first attempt, elastic host-loss replay is bitwise-identical to the
full-fleet run, and the goodput/recovery accounting in ResilienceReport
measures what was actually lost.
"""

import json
import os
import subprocess
import sys
import textwrap
import time
import urllib.error

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import stream
from repro.core.rsvd import rsvd_streamed
from repro.core.hosvd import rp_sthosvd_streamed
from repro.data import pipeline
from repro.stream import resilience as resil
from repro.stream.objectstore import (FileRangeFetcher, RetryPolicy,
                                      call_with_retry,
                                      is_transient_fetch_error)

jax.config.update("jax_platform_name", "cpu")

KEY = jax.random.PRNGKey(42)
ALL_METHODS = ["f32", "lowp_single", "shgemm", "shgemm3", "shgemm_pallas",
               "shgemm_fused"]

M, N, RANK = 96, 80, 8
TILE = 16                       # 6 tiles per pass
NOSLEEP = RetryPolicy(max_attempts=3, sleep=lambda s: None)


def _bitwise(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))


@pytest.fixture(scope="module")
def matrix():
    return np.asarray(jax.random.normal(jax.random.PRNGKey(1), (M, N),
                                        jnp.float32))


@pytest.fixture(scope="module")
def shard_dir(tmp_path_factory, matrix):
    d = tmp_path_factory.mktemp("resil_shards")
    pipeline.write_matrix_shards(d, matrix, 32)   # 3 shards, manifest.json
    return d


def _src(matrix):
    return stream.ArraySource(matrix, TILE)


# ---------------------------------------------------------------------------
# Payload serialization
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("left", [False, True])
def test_state_payload_roundtrip_bitwise(left):
    st = stream.init(KEY, N, 12, max_rows=M, left=left,
                     method="shgemm_fused")
    st = stream.update(st, jnp.ones((TILE, N), jnp.float32), 0)
    arrays, meta = resil.state_to_payload(st)
    # JSON round-trip the meta — exactly what the manifest does
    meta = json.loads(json.dumps(resil._jsonable(meta)))
    back = resil.state_from_payload(arrays, meta)
    assert np.array_equal(np.asarray(back.y), np.asarray(st.y))
    assert np.array_equal(np.asarray(back.key_omega),
                          np.asarray(st.key_omega))
    assert int(back.rows_seen) == int(st.rows_seen)
    assert (back.w is None) == (st.w is None)
    if left:
        assert np.array_equal(np.asarray(back.w), np.asarray(st.w))
    assert back.method == st.method and back.p == st.p
    # the restored state keeps absorbing identically
    blk = jnp.full((TILE, N), 0.5, jnp.float32)
    a1 = stream.update(st, blk, TILE)
    a2 = stream.update(back, blk, TILE)
    assert np.array_equal(np.asarray(a1.y), np.asarray(a2.y))


def test_tucker_payload_roundtrip_bitwise():
    ts = stream.tucker_init(KEY, (32, 10, 8), (5, 4, 3))
    ts = stream.tucker_update(ts, jnp.ones((8, 10, 8), jnp.float32), 0)
    arrays, meta = resil.tucker_to_payload(ts)
    meta = json.loads(json.dumps(resil._jsonable(meta)))
    back = resil.tucker_from_payload(arrays, meta)
    assert np.array_equal(np.asarray(back.z), np.asarray(ts.z))
    for m1, m2 in zip(ts.modes, back.modes):
        assert np.array_equal(np.asarray(m1.y), np.asarray(m2.y))
    assert back.dims == ts.dims and back.ranks == ts.ranks


# ---------------------------------------------------------------------------
# Checkpointed drivers: bitwise parity with the uninterrupted run
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ALL_METHODS)
def test_checkpointed_run_bitwise_all_methods(matrix, tmp_path, method):
    base = rsvd_streamed(KEY, _src(matrix), RANK, method=method)
    res, rep = rsvd_streamed(KEY, _src(matrix), RANK, method=method,
                             checkpoint_dir=tmp_path / method,
                             checkpoint_every_tiles=2, return_report=True)
    assert _bitwise(base, res)
    assert rep.attempts == 1 and rep.goodput == 1.0
    assert rep.tiles_recomputed == 0


@pytest.mark.parametrize("passes", [1, 2, 3, 4])
def test_resume_after_fault_bitwise(matrix, tmp_path, passes):
    """Kill mid-sketch with an injected exception; resume must reproduce
    the uninterrupted factors bit for bit with <= every_tiles replayed."""
    d = tmp_path / f"p{passes}"
    base = rsvd_streamed(KEY, _src(matrix), RANK, passes=passes)
    faulty = resil.FaultySource(_src(matrix), fail_at_tile=5, mode="raise")
    with pytest.raises(resil.FaultInjected):
        rsvd_streamed(KEY, faulty, RANK, passes=passes, checkpoint_dir=d,
                      checkpoint_every_tiles=2, resume=True)
    res, rep = rsvd_streamed(KEY, _src(matrix), RANK, passes=passes,
                             checkpoint_dir=d, checkpoint_every_tiles=2,
                             resume=True, return_report=True)
    assert _bitwise(base, res)
    assert rep.attempts == 2
    assert rep.tiles_recomputed <= 2          # <= checkpoint_every_tiles
    assert len(rep.recovery_events) == 1
    assert 0.0 < rep.goodput <= 1.0


def test_resume_during_b_pass_bitwise(matrix, tmp_path):
    """Fault during pass 2 (B accumulation): the sketch pass must NOT be
    replayed — resume restarts inside the B pass at a tile boundary."""
    n_tiles = M // TILE
    base = rsvd_streamed(KEY, _src(matrix), RANK)
    faulty = resil.FaultySource(_src(matrix), fail_at_tile=n_tiles + 2,
                                mode="raise")
    with pytest.raises(resil.FaultInjected):
        rsvd_streamed(KEY, faulty, RANK, checkpoint_dir=tmp_path,
                      checkpoint_every_tiles=2, resume=True)
    # the latest checkpoint is a B-phase checkpoint with a partial B
    man = json.loads((sorted(tmp_path.glob("ckpt_*"))[-1] /
                      "manifest.json").read_text())
    assert man["phase"] == "b" and "b" in man["arrays"]
    res = rsvd_streamed(KEY, _src(matrix), RANK, checkpoint_dir=tmp_path,
                        checkpoint_every_tiles=2, resume=True)
    assert _bitwise(base, res)


def test_resume_during_power_pass_bitwise(matrix, tmp_path):
    """passes >= 3 checkpoint at pass boundaries; a fault in pass 3
    resumes from the pass-2 basis, replaying at most one pass."""
    n_tiles = M // TILE
    base = rsvd_streamed(KEY, _src(matrix), RANK, passes=4)
    faulty = resil.FaultySource(_src(matrix), fail_at_tile=2 * n_tiles + 3,
                                mode="raise")
    with pytest.raises(resil.FaultInjected):
        rsvd_streamed(KEY, faulty, RANK, passes=4, checkpoint_dir=tmp_path,
                      checkpoint_every_tiles=2, resume=True)
    res = rsvd_streamed(KEY, _src(matrix), RANK, passes=4,
                        checkpoint_dir=tmp_path, checkpoint_every_tiles=2,
                        resume=True)
    assert _bitwise(base, res)


def test_checkpointed_tucker_bitwise(tmp_path):
    t = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (64, 12, 10),
                                     jnp.float32))
    base = rp_sthosvd_streamed(KEY, stream.ArraySource(t, 16),
                               ranks=(6, 5, 4))
    res, rep = rp_sthosvd_streamed(KEY, stream.ArraySource(t, 16),
                                   ranks=(6, 5, 4),
                                   checkpoint_dir=tmp_path / "a",
                                   checkpoint_every_tiles=1,
                                   return_report=True)
    assert np.array_equal(np.asarray(base.core), np.asarray(res.core))
    assert all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(base.factors, res.factors))
    assert rep.goodput == 1.0
    # fault + resume
    faulty = resil.FaultySource(stream.ArraySource(t, 16), fail_at_tile=2,
                                mode="raise")
    with pytest.raises(resil.FaultInjected):
        rp_sthosvd_streamed(KEY, faulty, ranks=(6, 5, 4),
                            checkpoint_dir=tmp_path / "b",
                            checkpoint_every_tiles=1, resume=True)
    res2 = rp_sthosvd_streamed(KEY, stream.ArraySource(t, 16),
                               ranks=(6, 5, 4),
                               checkpoint_dir=tmp_path / "b",
                               checkpoint_every_tiles=1, resume=True)
    assert np.array_equal(np.asarray(base.core), np.asarray(res2.core))


def test_fingerprint_mismatch_fails_loudly(matrix, tmp_path):
    faulty = resil.FaultySource(_src(matrix), fail_at_tile=4, mode="raise")
    with pytest.raises(resil.FaultInjected):
        rsvd_streamed(KEY, faulty, RANK, checkpoint_dir=tmp_path,
                      checkpoint_every_tiles=2, resume=True)
    with pytest.raises(RuntimeError, match="fingerprint mismatch"):
        rsvd_streamed(jax.random.PRNGKey(999), _src(matrix), RANK,
                      checkpoint_dir=tmp_path, checkpoint_every_tiles=2,
                      resume=True)


def test_no_resume_wipes_previous_job(matrix, tmp_path):
    faulty = resil.FaultySource(_src(matrix), fail_at_tile=4, mode="raise")
    with pytest.raises(resil.FaultInjected):
        rsvd_streamed(KEY, faulty, RANK, checkpoint_dir=tmp_path,
                      checkpoint_every_tiles=2, resume=True)
    assert list(tmp_path.glob("ckpt_*"))
    # resume=False: a NEW job, prior checkpoints cleared, attempts reset
    res, rep = rsvd_streamed(KEY, _src(matrix), RANK,
                             checkpoint_dir=tmp_path,
                             checkpoint_every_tiles=2, resume=False,
                             return_report=True)
    assert rep.attempts == 1 and not rep.recovery_events
    assert _bitwise(res, rsvd_streamed(KEY, _src(matrix), RANK))


def test_checkpoint_arg_validation(matrix, tmp_path):
    with pytest.raises(ValueError, match="checkpoint_dir"):
        rsvd_streamed(KEY, _src(matrix), RANK, resume=True)
    with pytest.raises(ValueError, match="checkpoint_dir"):
        rsvd_streamed(KEY, _src(matrix), RANK, checkpoint_every_tiles=2)
    with pytest.raises(ValueError, match="checkpoint_dir"):
        rsvd_streamed(KEY, _src(matrix), RANK, return_report=True)
    with pytest.raises(ValueError, match="adaptive"):
        rsvd_streamed(KEY, _src(matrix), RANK, tol=1e-2,
                      checkpoint_dir=tmp_path)
    with pytest.raises(ValueError, match="replayable"):
        rsvd_streamed(KEY, (matrix[i:i + TILE] for i in range(0, M, TILE)),
                      RANK, n_rows=M, n_cols=N, passes=1,
                      checkpoint_dir=tmp_path)


# ---------------------------------------------------------------------------
# SIGKILL + resume in a real subprocess (the acceptance test)
# ---------------------------------------------------------------------------

_KILL_SCRIPT = textwrap.dedent("""
    import json, sys
    import numpy as np
    import jax
    jax.config.update("jax_platform_name", "cpu")
    from repro import stream
    from repro.core.rsvd import rsvd_streamed
    from repro.stream import resilience as resil

    ckpt, shard_dir, fail_at = sys.argv[1], sys.argv[2], int(sys.argv[3])
    src = stream.DirectorySource(shard_dir, 16)
    if fail_at >= 0:
        src = resil.FaultySource(src, fail_at_tile=fail_at, mode="kill")
    res, rep = rsvd_streamed(jax.random.PRNGKey(11), src, 8,
                             checkpoint_dir=ckpt, checkpoint_every_tiles=2,
                             resume=True, return_report=True)
    np.savez(ckpt + "/result.npz", u=np.asarray(res.u),
             s=np.asarray(res.s), vt=np.asarray(res.vt))
    with open(ckpt + "/report.json", "w") as f:
        json.dump(rep.as_record(), f)
    print("RESILIENCE_OK")
""")


@pytest.mark.slow
def test_sigkill_and_resume_subprocess(matrix, shard_dir, tmp_path):
    """Attempt 1 is SIGKILLed mid-sketch (a real preemption: no atexit, no
    exception handling).  Attempt 2, same command line, resumes from disk
    and must produce factors bitwise-equal to an uninterrupted run, having
    recomputed at most checkpoint_every_tiles tiles."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    args = [sys.executable, "-c", _KILL_SCRIPT, str(tmp_path),
            str(shard_dir)]

    dead = subprocess.run(args + ["4"], env=env, capture_output=True,
                          text=True, timeout=600, cwd=root)
    assert dead.returncode == -9, (dead.returncode, dead.stderr[-2000:])
    assert (tmp_path / "heartbeat.json").is_file()
    assert list(tmp_path.glob("ckpt_*"))

    alive = subprocess.run(args + ["-1"], env=env, capture_output=True,
                           text=True, timeout=600, cwd=root)
    assert alive.returncode == 0, alive.stderr[-2000:]
    assert "RESILIENCE_OK" in alive.stdout

    base = rsvd_streamed(jax.random.PRNGKey(11),
                         stream.DirectorySource(shard_dir, 16), 8)
    got = np.load(tmp_path / "result.npz")
    assert np.array_equal(got["u"], np.asarray(base.u))
    assert np.array_equal(got["s"], np.asarray(base.s))
    assert np.array_equal(got["vt"], np.asarray(base.vt))

    rep = json.loads((tmp_path / "report.json").read_text())
    assert rep["attempts"] == 2
    assert rep["tiles_recomputed"] <= 2       # <= checkpoint_every_tiles
    assert len(rep["recovery_events"]) == 1
    assert 0.0 < rep["goodput"] <= 1.0
    log = json.loads((tmp_path / "resilience.json").read_text())
    assert log["finished"] is True


# ---------------------------------------------------------------------------
# Fault injection primitives
# ---------------------------------------------------------------------------

def test_faulty_source_raise_then_passthrough(matrix):
    fs = resil.FaultySource(_src(matrix), fail_at_tile=2, mode="raise")
    got = []
    with pytest.raises(resil.FaultInjected):
        for t in fs.tiles():
            got.append(np.asarray(t))
    assert len(got) == 2
    # n_faults exhausted: the NEXT replay passes through untouched
    tiles = [np.asarray(t) for t in fs.tiles()]
    assert np.array_equal(np.concatenate(tiles), matrix)


def test_faulty_source_counts_across_replays(matrix):
    """The tile counter is global across replays, so a fault can target
    the second pass of a two-pass driver."""
    n_tiles = M // TILE
    fs = resil.FaultySource(_src(matrix), fail_at_tile=n_tiles + 1,
                            mode="raise")
    assert len(list(fs.tiles())) == n_tiles          # pass 1 unscathed
    with pytest.raises(resil.FaultInjected):
        list(fs.tiles())                             # pass 2 dies at tile 1


def test_faulty_source_hang_then_yields(matrix):
    fs = resil.FaultySource(_src(matrix), fail_at_tile=1, mode="hang",
                            hang_secs=0.3)
    t0 = time.perf_counter()
    tiles = [np.asarray(t) for t in fs.tiles()]
    assert time.perf_counter() - t0 >= 0.3
    assert np.array_equal(np.concatenate(tiles), matrix)


def test_faulty_source_seed_deterministic(matrix):
    a = resil.FaultySource(_src(matrix), seed=7, mode="raise")
    b = resil.FaultySource(_src(matrix), seed=7, mode="raise")
    assert a.fail_at_tile == b.fail_at_tile
    assert 0 <= a.fail_at_tile < M // TILE


def test_faulty_source_validation(matrix):
    with pytest.raises(ValueError, match="mode"):
        resil.FaultySource(_src(matrix), fail_at_tile=0, mode="explode")
    with pytest.raises(ValueError, match="seed"):
        resil.FaultySource(_src(matrix))


def test_transient_classification():
    assert is_transient_fetch_error(TimeoutError())
    assert is_transient_fetch_error(ConnectionError())
    assert is_transient_fetch_error(
        urllib.error.HTTPError("u", 503, "x", None, None))
    assert not is_transient_fetch_error(
        urllib.error.HTTPError("u", 404, "x", None, None))
    assert not is_transient_fetch_error(ValueError("bad magic"))


def test_permanent_error_not_retried():
    calls = []

    def fn():
        calls.append(1)
        raise urllib.error.HTTPError("u", 404, "not found", None, None)

    with pytest.raises(urllib.error.HTTPError):
        call_with_retry(fn, url="u", what="read", policy=NOSLEEP)
    assert len(calls) == 1


@pytest.mark.parametrize("kind", ["timeout", "http503", "truncate"])
def test_flaky_fetcher_retry_then_succeed(matrix, shard_dir, kind):
    flaky = resil.FlakyRangeFetcher(FileRangeFetcher(), kind=kind)
    src = stream.ObjectStoreSource(shard_dir, tile_rows=TILE,
                                   fetcher=flaky, retry=NOSLEEP)
    flaky.fail_next(2, kind)           # attempts 0 and 1 fail, 2 succeeds
    tiles = [np.asarray(t) for t in src.tiles()]
    assert np.array_equal(np.concatenate(tiles), matrix)
    assert flaky.injected == 2


def test_flaky_fetcher_retry_exhausted_raises(matrix, shard_dir):
    flaky = resil.FlakyRangeFetcher(FileRangeFetcher())
    src = stream.ObjectStoreSource(shard_dir, tile_rows=TILE,
                                   fetcher=flaky, retry=NOSLEEP)
    flaky.fail_next(NOSLEEP.max_attempts)          # every attempt fails
    with pytest.raises(RuntimeError, match="3 attempts"):
        list(src.tiles())


def test_flaky_fetcher_rate_deterministic(shard_dir):
    a = resil.FlakyRangeFetcher(FileRangeFetcher(), rate=0.5, seed=3,
                                n_faults=2)
    b = resil.FlakyRangeFetcher(FileRangeFetcher(), rate=0.5, seed=3,
                                n_faults=2)
    url = str(sorted(shard_dir.glob("*.npy"))[0])
    outcomes_a, outcomes_b = [], []
    for f, out in ((a, outcomes_a), (b, outcomes_b)):
        for _ in range(8):
            try:
                f.read(url, 0, 16)
                out.append("ok")
            except TimeoutError:
                out.append("fault")
    assert outcomes_a == outcomes_b
    assert a.injected == 2                         # n_faults cap respected


# ---------------------------------------------------------------------------
# Elastic re-mesh
# ---------------------------------------------------------------------------

def test_partition_rows_tile_aligned():
    chunks = resil.partition_rows(100, 196, 3, tile_rows=16)
    assert chunks[0][0] == 100 and chunks[-1][1] == 196
    for (a0, a1), (b0, b1) in zip(chunks, chunks[1:]):
        assert a1 == b0                      # contiguous
    for a0, a1 in chunks[:-1]:
        assert (a1 - 100) % 16 == 0          # cuts on LOCAL tile boundaries
    assert len(chunks) <= 3
    # degenerate: range smaller than parts
    assert resil.partition_rows(0, 0, 4) == []
    small = resil.partition_rows(0, 10, 4, tile_rows=16)
    assert small == [(0, 10)]


def test_sketch_row_range_boundary_errors(matrix):
    st = stream.init(KEY, N, 12, max_rows=M, method="shgemm_fused")
    with pytest.raises(ValueError, match="boundar"):
        resil.sketch_row_range(st, _src(matrix), 8, 32)   # r0 mid-tile
    with pytest.raises(ValueError, match="outside"):
        resil.sketch_row_range(st, _src(matrix), 0, M + TILE)


@pytest.mark.parametrize("lose", [(1,), (0, 2)])
def test_elastic_host_loss_bitwise(matrix, lose):
    srcs = [stream.ArraySource(matrix[i * 32:(i + 1) * 32], TILE)
            for i in range(3)]
    full = resil.elastic_distributed_rsvd_streamed(KEY, srcs, RANK)
    res, rep = resil.elastic_distributed_rsvd_streamed(
        KEY, srcs, RANK, lose_hosts=lose, lose_after_tiles=1,
        return_report=True)
    assert _bitwise(full, res)
    assert len(rep.recovery_events) == len(lose)
    assert rep.tiles_recomputed >= len(lose) * 32 // TILE
    assert 0.0 < rep.goodput < 1.0
    assert all(e["time_to_recover_s"] is not None
               for e in rep.recovery_events)
    # same tiling single-host run is also bitwise-identical
    single = rsvd_streamed(KEY, _src(matrix), RANK)
    assert _bitwise(single, full)


def test_elastic_rejects_single_pass(matrix):
    srcs = [stream.ArraySource(matrix[:48], TILE),
            stream.ArraySource(matrix[48:], TILE)]
    with pytest.raises(ValueError, match="passes >= 2"):
        resil.elastic_distributed_rsvd_streamed(KEY, srcs, RANK, passes=1)
    with pytest.raises(ValueError, match="survivors"):
        resil.elastic_distributed_rsvd_streamed(KEY, srcs, RANK,
                                                lose_hosts=(0, 1))


# ---------------------------------------------------------------------------
# Distributed driver checkpointing (virtual 2-host mesh -> subprocess)
# ---------------------------------------------------------------------------

_DIST_SCRIPT = textwrap.dedent("""
    import numpy as np
    import jax, jax.numpy as jnp
    from repro import stream
    from repro.core.distributed import distributed_rsvd_streamed
    from repro.launch.mesh import make_mesh
    from repro.stream import resilience as resil
    import sys, tempfile
    from pathlib import Path

    assert len(jax.devices()) == 2
    mesh = make_mesh((2,), ("data",))
    key = jax.random.PRNGKey(0)
    a = np.asarray(jax.random.normal(jax.random.fold_in(key, 1), (96, 64),
                                     jnp.float32))
    srcs = [stream.ArraySource(a[:48], 16), stream.ArraySource(a[48:], 16)]
    base = distributed_rsvd_streamed(key, srcs, 8, mesh)

    d = Path(tempfile.mkdtemp())
    res, rep = distributed_rsvd_streamed(key, srcs, 8, mesh,
                                         checkpoint_dir=d,
                                         checkpoint_every_tiles=2,
                                         return_report=True)
    assert all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(base, res)), "ckpt run != plain run"
    assert rep.goodput == 1.0

    # fault mid-sketch on host 1, resume, bitwise
    d2 = Path(tempfile.mkdtemp())
    faulty = [stream.ArraySource(a[:48], 16),
              resil.FaultySource(stream.ArraySource(a[48:], 16),
                                 fail_at_tile=1, mode="raise")]
    try:
        distributed_rsvd_streamed(key, faulty, 8, mesh, checkpoint_dir=d2,
                                  checkpoint_every_tiles=2, resume=True)
        raise SystemExit("fault did not fire")
    except resil.FaultInjected:
        pass
    res2, rep2 = distributed_rsvd_streamed(key, srcs, 8, mesh,
                                           checkpoint_dir=d2,
                                           checkpoint_every_tiles=2,
                                           resume=True, return_report=True)
    assert all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(base, res2)), "resumed run != plain run"
    assert rep2.attempts == 2
    print("DIST_RESIL_OK")
""")


@pytest.mark.slow
def test_distributed_checkpoint_subprocess():
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", _DIST_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=root)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "DIST_RESIL_OK" in out.stdout


# ---------------------------------------------------------------------------
# tiles_from resume-cursor contract (all source kinds)
# ---------------------------------------------------------------------------

def test_tiles_from_suffix_contract(matrix, shard_dir):
    kinds = {
        "array": stream.ArraySource(matrix, TILE),
        "directory": stream.DirectorySource(shard_dir, TILE),
        "objectstore": stream.ObjectStoreSource(shard_dir,
                                                tile_rows=TILE),
    }
    for name, src in kinds.items():
        full = [np.asarray(t) for t in src.tiles()]
        for k in (0, 2, len(full)):
            start = k * TILE
            suffix = [np.asarray(t) for t in src.tiles_from(start)]
            assert len(suffix) == len(full) - k, (name, k)
            for a, b in zip(full[k:], suffix):
                assert np.array_equal(a, b), (name, k)
        with pytest.raises(ValueError, match="boundar"):
            list(src.tiles_from(TILE // 2))
        with pytest.raises(ValueError, match="out of range"):
            list(src.tiles_from(-1))
        with pytest.raises(ValueError, match="out of range"):
            list(src.tiles_from(M + TILE))
