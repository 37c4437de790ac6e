"""Vocab-parallel embedding lookup + CE (shard_map) must match the plain
single-device path bit-for-bit in math (loss AND gradients) — run on an
8-virtual-device mesh in a subprocess."""

import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import smoke_config
    from repro.models import registry as R, transformer as T
    from repro.sharding import activation as A
    from repro.launch.mesh import make_mesh

    cfg = smoke_config(R.get_arch("qwen3-0.6b"))
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    B, S = 8, 16
    batch = {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab, jnp.int32),
        "labels": jax.random.randint(jax.random.fold_in(key, 1), (B, S), 0,
                                     cfg.vocab, jnp.int32),
    }

    def loss(p, b):
        return T.loss_fn(cfg, p, b)

    # reference: no mesh (plain gather / take_along_axis)
    A.set_mesh(None)
    l_ref, g_ref = jax.value_and_grad(loss)(params, batch)

    # vocab-parallel: 4x2 mesh, shard_map paths
    mesh = make_mesh((4, 2), ("data", "model"))
    A.set_mesh(mesh, tp=False)
    l_vp, g_vp = jax.value_and_grad(loss)(params, batch)
    A.set_mesh(None)

    np.testing.assert_allclose(float(l_ref), float(l_vp), rtol=2e-5)
    for k in g_ref:
        a, b = np.asarray(g_ref[k], np.float32), np.asarray(g_vp[k], np.float32)
        # max-norm relative: different collective orders reassociate bf16 sums
        rel = np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-12)
        assert rel < 2e-2, (k, rel)
    print("VP_OK", float(l_ref), float(l_vp))

    # also with TP on.  Looser than the VP check: TP reassociates the bf16
    # contraction over the model axis (same reason as the gradient check
    # above), which lands ~1e-4 relative on XLA-CPU.
    A.set_mesh(mesh, tp=True)
    l_tp = loss(params, batch)
    A.set_mesh(None)
    np.testing.assert_allclose(float(l_ref), float(l_tp), rtol=5e-4)
    print("TP_OK")
""")


@pytest.mark.slow
def test_vocab_parallel_matches_reference():
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, (out.stderr[-3000:], out.stdout[-500:])
    assert "VP_OK" in out.stdout and "TP_OK" in out.stdout
