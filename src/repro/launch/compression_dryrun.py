"""Compiled-artifact proof of the paper-technique DP compression.

Lowers two gradient-reduction programs on the multi-pod (2,16,16) mesh and
counts collective bytes in the compiled HLO:

  raw:      g_reduced = psum(g, "pod")                   (full f32 grads)
  sketched: Q = qr(Omega_bf16); psum(Q^T g, "pod")       (rank-r sketch;
            un-projected locally, error-feedback residual stays device-local)

The wire ratio should be ~d/r on the pod (DCN) axis — the paper's random
projection applied to the distributed-optimization layer (DESIGN.md §4.2).

    PYTHONPATH=src python -m repro.launch.compression_dryrun
"""

import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

# ruff: noqa: E402
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch import dryrun as DR
from repro.launch import mesh as mesh_mod


def main(d: int = 8192, cols: int = 4096, rank: int = 64):
    mesh = mesh_mod.make_production_mesh(multi_pod=True)
    g_spec = NamedSharding(mesh, P(None, ("data", "model")))
    g_abs = jax.ShapeDtypeStruct((d, cols), jnp.float32)

    def raw(g):
        def f(gl):
            return jax.lax.psum(gl, "pod")
        return jax.shard_map(f, mesh=mesh,
                          in_specs=P(None, ("data", "model")),
                          out_specs=P(None, ("data", "model")),
                          check_vma=False)(g)

    def sketched(g):
        def f(gl):
            omega = jax.random.normal(jax.random.PRNGKey(0), (d, rank),
                                      jnp.float32)
            q, _ = jnp.linalg.qr(omega)
            sk = jnp.dot(q.astype(jnp.bfloat16).T.astype(jnp.float32), gl)
            sk = jax.lax.psum(sk, "pod")          # rank-r rows on the wire
            return jnp.dot(q, sk)
        return jax.shard_map(f, mesh=mesh,
                          in_specs=P(None, ("data", "model")),
                          out_specs=P(None, ("data", "model")),
                          check_vma=False)(g)

    rows = []
    for name, fn in (("raw_psum", raw), ("sketched_psum", sketched)):
        compiled = jax.jit(fn, in_shardings=(g_spec,),
                           out_shardings=g_spec).lower(g_abs).compile()
        coll = DR.collective_bytes(compiled.as_text())
        wire = (coll["all-gather"] + 2 * coll["all-reduce"]
                + coll["reduce-scatter"] + coll["all-to-all"]
                + coll["collective-permute"])
        rows.append((name, wire))
        print(f"{name:14s} wire={wire/1e6:10.2f} MB/device  ({coll})")
    ratio = rows[0][1] / max(rows[1][1], 1)
    print(f"wire reduction: {ratio:.1f}x  (d/r = {d/rank:.0f})")
    return rows


if __name__ == "__main__":
    main()
