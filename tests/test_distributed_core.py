"""Distributed RandNLA (shard_map) on a virtual 8-device host mesh.

Needs XLA_FLAGS=--xla_force_host_platform_device_count=8, which must be set
before jax initializes — so these run in a subprocess (the main pytest
process keeps the 1-device view per the dry-run isolation rule).
"""

import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.core import distributed as D, rsvd
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4, 2), ("data", "model"))
    assert len(jax.devices()) == 8
    key = jax.random.PRNGKey(0)
    a = rsvd.matrix_with_singular_values(
        key, 512, rsvd.singular_values_exp(512, 48, 1e-5))
    a_sh = D.shard_matrix(a, mesh)

    res = D.distributed_rsvd(jax.random.PRNGKey(1), a_sh, 48, mesh)
    approx = (res.u * res.s[None, :]) @ res.vt
    err = float(jnp.linalg.norm(a - approx) / jnp.linalg.norm(a))
    # TSQR-of-B^T path matches single-device accuracy (no Gram squaring)
    assert err < 1e-4, err

    # singular values match the single-device implementation
    res1 = rsvd.rsvd(jax.random.PRNGKey(1), a, 48)
    np.testing.assert_allclose(np.asarray(res.s[:16]), np.asarray(res1.s[:16]),
                               rtol=1e-2)

    # range finder orthonormality across shards
    q = D.distributed_range_finder(jax.random.PRNGKey(2), a_sh, 58, mesh)
    qtq = np.asarray(q.T @ q)
    np.testing.assert_allclose(qtq, np.eye(58), atol=1e-4)

    # power iteration closes in on the Eckart-Young floor for a flat spectrum
    s_flat = rsvd.singular_values_linear(512, 48, 0.5)
    a2 = rsvd.matrix_with_singular_values(jax.random.PRNGKey(3), 512, s_flat)
    a2_sh = D.shard_matrix(a2, mesh)
    floor = float(jnp.linalg.norm(s_flat[48:]) / jnp.linalg.norm(s_flat))
    res0 = D.distributed_rsvd(jax.random.PRNGKey(4), a2_sh, 48, mesh)
    res2 = D.distributed_rsvd(jax.random.PRNGKey(4), a2_sh, 48, mesh,
                              power_iters=2)
    def relerr(r):
        ap = (r.u * r.s[None, :]) @ r.vt
        return float(jnp.linalg.norm(a2 - ap) / jnp.linalg.norm(a2))
    assert relerr(res2) < relerr(res0)
    assert relerr(res2) < 1.02 * floor, (relerr(res2), floor)

    # fused method: each device generates its Omega row-block IN-KERNEL from
    # (key, global column offset) — nothing materialized or communicated for
    # the random matrix (DESIGN.md §9/§10).
    from jax.sharding import PartitionSpec as P
    from repro.core.projection import fused_omega
    from repro.kernels import ops, shgemm_fused as kf

    res_f = D.distributed_rsvd(jax.random.PRNGKey(1), a_sh, 48, mesh,
                               method="shgemm_fused")
    approx_f = (res_f.u * res_f.s[None, :]) @ res_f.vt
    err_f = float(jnp.linalg.norm(a - approx_f) / jnp.linalg.norm(a))
    assert err_f < 1e-4, err_f
    res_f1 = rsvd.rsvd(jax.random.PRNGKey(1), a, 48, method="shgemm_fused")
    np.testing.assert_allclose(np.asarray(res_f.s[:16]),
                               np.asarray(res_f1.s[:16]), rtol=1e-2)
    qf = D.distributed_range_finder(jax.random.PRNGKey(2), a_sh, 58, mesh,
                                    method="shgemm_fused")
    np.testing.assert_allclose(np.asarray(qf.T @ qf), np.eye(58), atol=1e-4)

    # the sharded fused projection equals the one-shot projection on the
    # materialized counter-stream Omega up to f32 psum ordering alone
    fnp = jax.shard_map(
        lambda blk, k2: D._local_sketch_fused(blk, k2, 58, "model"),
        mesh=mesh, in_specs=(P("data", "model"), P(None, None)),
        out_specs=P("data", None), check_vma=False)
    y = fnp(a_sh, kf.key_words(jax.random.PRNGKey(2)))
    y_ref = ops.shgemm(a, fused_omega(jax.random.PRNGKey(2), (512, 58),
                                      dtype=jnp.bfloat16))
    rel = float(jnp.linalg.norm(y - y_ref) / jnp.linalg.norm(y_ref))
    assert rel < 1e-5, rel
    print("DISTRIBUTED_OK", err, err_f, rel)
""")


@pytest.mark.slow
def test_distributed_rsvd_8dev():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "DISTRIBUTED_OK" in out.stdout
