"""Multi-host × out-of-core streaming (stream.merge_across_hosts +
distributed_rsvd_streamed) on a virtual 2-device host mesh.

Needs XLA_FLAGS=--xla_force_host_platform_device_count=2 set before jax
initializes, so the assertions run in a subprocess (the main pytest
process keeps the 1-device view — same pattern as
tests/test_distributed_core.py).
"""

import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import numpy as np
    import jax, jax.numpy as jnp

    from repro import stream
    from repro.core import distributed as D, rsvd
    from repro.launch.mesh import make_mesh
    from repro.data import pipeline

    assert len(jax.devices()) == 2
    mesh = make_mesh((2,), ("hosts",))
    key = jax.random.PRNGKey(0)
    m, n, rank = 128, 96, 12
    a = jax.random.normal(jax.random.fold_in(key, 1), (m, n), jnp.float32)
    p_hat = rank + 10

    def _merge_on_mesh(states):
        return D._shard_map_stack(
            lambda st: stream.merge_across_hosts(st, "hosts"),
            states, mesh, "hosts")

    # --- merge_across_hosts == single-host sketch of the concatenated
    # matrix, bit for bit (2 simulated hosts, disjoint global row halves,
    # uneven tilings per host)
    states = []
    for lo, hi, tile in [(0, 64, 24), (64, 128, 32)]:
        st = stream.init(key, n, p_hat, max_rows=m, left=True)
        for off in range(lo, hi, tile):
            st = stream.update(st, a[off:off + min(tile, hi - off)], off)
        states.append(st)
    merged = _merge_on_mesh(states)
    seq = stream.init(key, n, p_hat, max_rows=m, left=True)
    for lo, hi, tile in [(0, 64, 24), (64, 128, 32)]:
        for off in range(lo, hi, tile):
            seq = stream.update(seq, a[off:off + min(tile, hi - off)], off)
    np.testing.assert_array_equal(np.asarray(merged.y), np.asarray(seq.y))
    # W accumulates (add semantics): psum == the same two-term addition
    np.testing.assert_allclose(np.asarray(merged.w), np.asarray(seq.w),
                               rtol=1e-6, atol=1e-6)
    assert int(merged.rows_seen) == m

    # --- key congruence guard: different Omega keys across hosts must
    # poison the merged sketch with NaN, not return a silent garbage sum
    bad = stream.init(jax.random.PRNGKey(9), n, p_hat, max_rows=m,
                      left=True)
    bad = stream.update(bad, a[64:128], 64)
    poisoned = _merge_on_mesh([states[0], bad])
    assert np.isnan(np.asarray(poisoned.y)).all()

    # --- end-to-end: distributed_rsvd_streamed over per-host .npy shard
    # dirs (the object-store layout) vs single-host rsvd_streamed with the
    # identical global tiling — the sketch pass is bitwise, the factor
    # passes add one psum reassociation (~1 ulp)
    import tempfile, os
    td = tempfile.mkdtemp()
    pipeline.write_matrix_shards(os.path.join(td, "h0"), np.asarray(a[:64]), 24)
    pipeline.write_matrix_shards(os.path.join(td, "h1"), np.asarray(a[64:]), 24)
    srcs = [stream.DirectorySource(os.path.join(td, "h0"), 24),
            stream.DirectorySource(os.path.join(td, "h1"), 24)]
    res_d = D.distributed_rsvd_streamed(key, srcs, rank, mesh,
                                        data_axis="hosts")

    def tiles():
        for lo, hi in [(0, 64), (64, 128)]:
            for off in range(lo, hi, 24):
                yield a[off:off + min(24, hi - off)]
    res_s = rsvd.rsvd_streamed(key, tiles, rank, n_rows=m, n_cols=n)
    np.testing.assert_allclose(np.asarray(res_d.u), np.asarray(res_s.u),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(res_d.s), np.asarray(res_s.s),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(res_d.vt), np.asarray(res_s.vt),
                               rtol=1e-4, atol=1e-5)
    err_d = float(rsvd.reconstruction_error(a, res_d))
    err_1 = float(rsvd.reconstruction_error(
        a, rsvd.rsvd(key, a, rank, method="shgemm_fused")))
    assert abs(err_d - err_1) <= 1e-5, (err_d, err_1)

    # streamed power iteration distributes too: passes=4 == in-core
    # power_iters=1 accuracy
    res_d4 = D.distributed_rsvd_streamed(key, srcs, rank, mesh,
                                         data_axis="hosts", passes=4)
    err_d4 = float(rsvd.reconstruction_error(a, res_d4))
    err_p1 = float(rsvd.reconstruction_error(
        a, rsvd.rsvd(key, a, rank, method="shgemm_fused", power_iters=1)))
    assert abs(err_d4 - err_p1) <= 1e-5, (err_d4, err_p1)
    assert err_d4 <= err_d * 1.02 + 2e-7

    # validation: source/mesh mismatch and unreplayable sources fail loudly
    try:
        D.distributed_rsvd_streamed(key, srcs[:1], rank, mesh,
                                    data_axis="hosts")
        raise SystemExit("expected source-count mismatch error")
    except ValueError as e:
        assert "mesh axis" in str(e), e
    gen = stream.GeneratorSource(iter([np.asarray(a[:64])]), (64, n))
    try:
        D.distributed_rsvd_streamed(key, [gen, srcs[1]], rank, mesh,
                                    data_axis="hosts")
        raise SystemExit("expected replayability error")
    except ValueError as e:
        assert "replay" in str(e), e
    print("DISTRIBUTED_STREAM_OK", err_d, err_d4)
""")


@pytest.mark.slow
def test_merge_across_hosts_2dev():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "DISTRIBUTED_STREAM_OK" in out.stdout
