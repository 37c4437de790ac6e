"""Distributed RSVD on a (data, model) mesh — shard_map SUMMA projection +
TSQR (DESIGN.md §6).  The mesh spans every device JAX finds: the chips of a
TPU host, or virtual CPU devices:

    PYTHONPATH=src python examples/distributed_rsvd.py          # TPU host
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      JAX_PLATFORMS=cpu PYTHONPATH=src python examples/distributed_rsvd.py
"""

import jax
import jax.numpy as jnp

from repro.core import distributed as D, rsvd
from repro.launch.mesh import make_host_mesh


def main():
    mesh = make_host_mesh(model_parallel=2)
    print(f"devices: {len(jax.devices())}, mesh: {dict(mesh.shape)}")

    key = jax.random.PRNGKey(0)
    n, rank = 1024, 64
    a = rsvd.matrix_with_singular_values(
        key, n, rsvd.singular_values_exp(n, rank, 1e-5))
    a_sharded = D.shard_matrix(a, mesh)
    print("A sharding:", a_sharded.sharding.spec)

    res = D.distributed_rsvd(jax.random.PRNGKey(1), a_sharded, rank, mesh)
    approx = (res.u * res.s[None, :]) @ res.vt
    err = float(jnp.linalg.norm(a - approx) / jnp.linalg.norm(a))
    print(f"distributed rsvd rank {rank}: rel_err={err:.3e}")
    print("U sharding:", res.u.sharding.spec, " V^T sharding:",
          res.vt.sharding.spec)

    ref = rsvd.rsvd(jax.random.PRNGKey(1), a, rank)
    print("sigma (distributed):", [f"{float(x):.4f}" for x in res.s[:5]])
    print("sigma (single-dev): ", [f"{float(x):.4f}" for x in ref.s[:5]])


if __name__ == "__main__":
    main()
