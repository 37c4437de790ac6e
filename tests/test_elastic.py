"""Elastic re-mesh: reshard live params onto a smaller/larger device set."""

import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.train.loop import remesh
    from repro.launch.mesh import make_mesh

    devs = jax.devices()
    assert len(devs) == 8

    # start on all 8 devices
    mesh8 = make_mesh((8, 1), ("data", "model"))
    params = {"w": jax.device_put(
        jnp.arange(64.0).reshape(8, 8),
        NamedSharding(mesh8, P("data", None)))}

    # "lose" 4 devices -> rebuild on the survivors
    survivors = devs[:4]
    specs_fn = lambda mesh: {"w": P("data", None)}
    mesh4, placed = remesh(params, specs_fn, new_devices=survivors)
    assert placed["w"].sharding.device_set == set(survivors)
    np.testing.assert_array_equal(np.asarray(placed["w"]),
                                  np.arange(64.0).reshape(8, 8))

    # scale back up to 8
    mesh8b, placed8 = remesh(placed, specs_fn, new_devices=devs)
    assert len(placed8["w"].sharding.device_set) == 8
    np.testing.assert_array_equal(np.asarray(placed8["w"]),
                                  np.arange(64.0).reshape(8, 8))
    print("ELASTIC_OK")
""")


@pytest.mark.slow
def test_remesh_shrink_and_grow():
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ELASTIC_OK" in out.stdout
