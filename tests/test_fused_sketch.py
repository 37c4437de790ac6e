"""Fused RNG+SHGEMM kernel (kernels/shgemm_fused.py): the determinism
contract, in-kernel sample statistics, numerical agreement with the
materialized-Omega path, and end-to-end RandNLA consumers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import projection as proj
from repro.core import rsvd
from repro.kernels import ops, shgemm_fused as kf

jax.config.update("jax_platform_name", "cpu")

KEY = jax.random.PRNGKey(42)


# ---------------------------------------------------------------------------
# Determinism contract
# ---------------------------------------------------------------------------

def test_bit_identical_across_block_shapes():
    """Same key => bit-identical C across block configs sharing bk (the
    Omega bits are block-invariant; f32 K-accumulation order is fixed by bk).
    This is the acceptance-criteria property."""
    m, k, n = 96, 300, 70
    a = jax.random.normal(jax.random.PRNGKey(7), (m, k), jnp.float32)
    y_ref = ops.shgemm_fused(a, KEY, n, blocks=(32, 128, 128))
    for blocks in [(96, 256, 128), (8, 128, 128), (64, 128, 128)]:
        y = ops.shgemm_fused(a, KEY, n, blocks=blocks)
        np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y),
                                      err_msg=f"blocks={blocks}")


def test_close_across_bk():
    """Across different bk the Omega bits are still identical; C differs only
    by f32 summation order."""
    m, k, n = 64, 512, 64
    a = jax.random.normal(jax.random.PRNGKey(8), (m, k), jnp.float32)
    y1 = ops.shgemm_fused(a, KEY, n, blocks=(32, 128, 128))
    y2 = ops.shgemm_fused(a, KEY, n, blocks=(32, 128, 256))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-6, atol=1e-5)


def test_key_sensitivity():
    m, k, n = 32, 256, 64
    a = jax.random.normal(jax.random.PRNGKey(9), (m, k), jnp.float32)
    y1 = ops.shgemm_fused(a, KEY, n)
    y2 = ops.shgemm_fused(a, jax.random.PRNGKey(43), n)
    assert not np.array_equal(np.asarray(y1), np.asarray(y2))


def test_padding_invariance():
    """The result for the valid region must not depend on how much padding
    the block shape forces (pad rows of A null the extra Omega rows)."""
    m, k, n = 50, 130, 30
    a = jax.random.normal(jax.random.PRNGKey(10), (m, k), jnp.float32)
    y_small = ops.shgemm_fused(a, KEY, n, blocks=(8, 128, 128))
    y_large = ops.shgemm_fused(a, KEY, n, blocks=(256, 512, 128))
    np.testing.assert_array_equal(np.asarray(y_small), np.asarray(y_large))


# ---------------------------------------------------------------------------
# In-kernel sample statistics (pre-rounding stream)
# ---------------------------------------------------------------------------

def test_gaussian_moments():
    """Box-Muller from hashed 24-bit uniforms: mean ~ 0, var ~ 1."""
    g = np.asarray(kf.reference_omega(KEY, (512, 512)))
    nsamp = g.size
    assert abs(g.mean()) < 5.0 / np.sqrt(nsamp)
    assert abs(g.var() - 1.0) < 5.0 * np.sqrt(2.0 / nsamp)
    # rows and columns are independent streams: no rank-1 structure
    corr = np.corrcoef(g[0], g[1])[0, 1]
    assert abs(corr) < 5.0 / np.sqrt(g.shape[1])


def test_gaussian_tail_sanity():
    g = np.asarray(kf.reference_omega(KEY, (512, 512)))
    frac_2sigma = float(np.mean(np.abs(g) < 2.0))
    assert abs(frac_2sigma - 0.9545) < 0.01
    assert np.all(np.isfinite(g))


def test_achlioptas_fused_values_and_density():
    sp = np.asarray(kf.reference_omega(KEY, (1024, 64), dist="achlioptas"))
    assert set(np.unique(sp)).issubset({-1.0, 0.0, 1.0})
    density = float((sp != 0).mean())
    assert abs(density - 1.0 / 3.0) < 0.02  # s=3 -> density 1/s
    # symmetric signs
    assert abs((sp == 1).mean() - (sp == -1).mean()) < 0.02


def test_very_sparse_fused_density():
    k = 4096
    sp = np.asarray(kf.reference_omega(KEY, (k, 64), dist="very_sparse"))
    density = float((sp != 0).mean())
    assert 0.5 / np.sqrt(k) < density < 2.0 / np.sqrt(k)


# ---------------------------------------------------------------------------
# Agreement with the materialized-Omega paths
# ---------------------------------------------------------------------------

def test_fused_equals_materialized_pallas():
    """Fused kernel == shgemm on the equivalently-generated Omega, bit for
    bit (same blocks => identical accumulation order)."""
    m, k, n = 96, 300, 70
    blocks = (32, 128, 128)
    a = jax.random.normal(jax.random.PRNGKey(11), (m, k), jnp.float32)
    y_fused = ops.shgemm_fused(a, KEY, n, blocks=blocks)
    omega = proj.fused_omega(KEY, (k, n), dtype=jnp.bfloat16)
    y_mat = ops.shgemm(a, omega, blocks=blocks)
    np.testing.assert_array_equal(np.asarray(y_fused), np.asarray(y_mat))


def test_fused_accuracy_vs_f64_oracle():
    """Acceptance criterion: fused rel. Frobenius error vs the f64 oracle
    within 1.1x of the materialized shgemm path on the same Omega
    (Fig. 5 setup: A ~ N(0,1))."""
    m, k, n = 256, 1024, 128
    a = jax.random.normal(jax.random.PRNGKey(12), (m, k), jnp.float32)
    omega = proj.fused_omega(KEY, (k, n), dtype=jnp.bfloat16)
    oracle = np.asarray(a, np.float64) @ np.asarray(omega, np.float64)

    def rel(c):
        c = np.asarray(c, np.float64)
        return np.linalg.norm(c - oracle) / np.linalg.norm(oracle)

    e_fused = rel(ops.shgemm_fused(a, KEY, n))
    e_mat = rel(proj.project(a, omega, method="shgemm"))
    assert e_fused <= 1.1 * e_mat + 1e-12, (e_fused, e_mat)
    assert e_fused < 1e-5  # fp32-level regime (paper Eq. 40)


@pytest.mark.parametrize("dist", ["achlioptas", "very_sparse"])
def test_fused_sparse_dists_match(dist):
    m, k, n = 64, 256, 48
    blocks = (8, 128, 128)
    a = jax.random.normal(jax.random.PRNGKey(13), (m, k), jnp.float32)
    y_fused = ops.shgemm_fused(a, KEY, n, dist=dist, blocks=blocks)
    omega = proj.fused_omega(KEY, (k, n), dist=dist, dtype=jnp.bfloat16)
    y_mat = ops.shgemm(a, omega, blocks=blocks)
    np.testing.assert_array_equal(np.asarray(y_fused), np.asarray(y_mat))


@pytest.mark.parametrize("fp8", [jnp.float8_e4m3fn, jnp.float8_e5m2])
def test_fp8_omega_dtype_rounds_through_storage(fp8):
    """omega_dtype=fp8 must quantize the in-kernel samples through the fp8
    grid (storage-only, consumed as bf16) — exactly matching project() on a
    materialized fp8 fused_omega, and differing from the plain bf16 path."""
    m, k, n = 64, 256, 48
    blocks = (8, 128, 128)
    a = jax.random.normal(jax.random.PRNGKey(21), (m, k), jnp.float32)
    y8 = ops.shgemm_fused(a, KEY, n, omega_dtype=fp8, blocks=blocks)
    om8 = proj.fused_omega(KEY, (k, n), dtype=fp8)
    assert om8.dtype == fp8
    want = ops.shgemm(a, om8.astype(jnp.bfloat16), blocks=blocks)
    np.testing.assert_array_equal(np.asarray(y8), np.asarray(want))
    ybf = ops.shgemm_fused(a, KEY, n, omega_dtype=jnp.bfloat16, blocks=blocks)
    assert not np.array_equal(np.asarray(y8), np.asarray(ybf))
    with pytest.raises(TypeError):
        ops.shgemm_fused(a, KEY, n, omega_dtype=jnp.float32)


def test_block_resolution_not_baked_into_trace(monkeypatch):
    """Block selection must run on every untuned call (outside jit), so a
    mid-process autotune cache update can take effect."""
    from repro.kernels import autotune
    calls = []
    real = autotune.pick_blocks

    def spy(*args, **kw):
        calls.append(args)
        return real(*args, **kw)

    monkeypatch.setattr(autotune, "pick_blocks", spy)
    a = jax.random.normal(jax.random.PRNGKey(22), (16, 128), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(23), (128, 32),
                          jnp.float32).astype(jnp.bfloat16)
    ops.shgemm(a, b)
    ops.shgemm(a, b)
    assert len(calls) == 2
    ops.shgemm_fused(a, KEY, 32)
    ops.shgemm_fused(a, KEY, 32)
    assert len(calls) == 4


def test_fp16_fused_path():
    m, k, n = 64, 256, 48
    a = jax.random.normal(jax.random.PRNGKey(14), (m, k), jnp.float32)
    y = ops.shgemm_fused(a, KEY, n, omega_dtype=jnp.float16)
    omega = proj.fused_omega(KEY, (k, n), dtype=jnp.float16)
    want = proj.project(a, omega, method="shgemm")
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_fp16_refused_compiled_kept_in_interpret_mode():
    """The fp16 decision (DESIGN.md §2): compiled Pallas kernels refuse an
    fp16 Omega before tracing, naming bf16 and the XLA ``shgemm`` method;
    interpret mode keeps fp16 for validation (``test_fp16_fused_path``)."""
    a = jax.random.normal(jax.random.PRNGKey(16), (64, 256), jnp.float32)
    b16 = proj.fused_omega(KEY, (256, 48), dtype=jnp.float16)
    with pytest.raises(ValueError, match="bfloat16.*method='shgemm'"):
        ops.shgemm(a, b16, interpret=False)
    with pytest.raises(ValueError, match="bfloat16.*method='shgemm'"):
        ops.shgemm_fused(a, KEY, 48, omega_dtype=jnp.float16,
                         interpret=False)
    y = ops.shgemm(a, b16, interpret=True)
    want = proj.project(a, b16, method="shgemm")
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


# sha256 of reference_omega(PRNGKey(1234), (64, 48), row_offset=256,
# col_offset=8) in f32, recorded when _uniform24 converted uint32 -> f32
# directly; the int32 route Mosaic can lower must give the same bits.
_OMEGA_SHA256 = {
    "gaussian":
        "8f04f1391d8241b37c87c8109429d916f6131dd8c1bdde102f8d8085255834bf",
    "achlioptas":
        "1f5f3454cf7b4bbdbf9186da4bc185f57cb26271dab88b4614cb4669903b2ce3",
    "very_sparse":
        "3cdc26b6c87ef946729769a18f9ba10be6bf18a4c579d41592e7d73d15040f9e",
}


@pytest.mark.parametrize("dist", sorted(_OMEGA_SHA256))
def test_uniform24_int32_route_keeps_omega_bits(dist):
    import hashlib
    omega = kf.reference_omega(jax.random.PRNGKey(1234), (64, 48), dist=dist,
                               dtype=jnp.float32, row_offset=256,
                               col_offset=8)
    digest = hashlib.sha256(np.asarray(omega).tobytes()).hexdigest()
    assert digest == _OMEGA_SHA256[dist]


def test_uniform24_exact_at_extremes():
    bits = jnp.asarray([0, 0xFF, 0x100, 0x7FFFFFFF, 0x80000000, 0xFFFFFFFF],
                       jnp.uint32)
    want = (np.asarray(bits, np.uint64) >> 8).astype(np.float64) * 2.0**-24
    np.testing.assert_array_equal(np.asarray(kf._uniform24(bits)),
                                  want.astype(np.float32))


# ---------------------------------------------------------------------------
# Consumers
# ---------------------------------------------------------------------------

def test_sketch_front_door_legacy_unchanged():
    """proj.sketch with a non-fused method reproduces the old
    gaussian+project composition exactly (no behavior change for callers)."""
    n, p = 128, 16
    a = jax.random.normal(jax.random.PRNGKey(15), (n, n), jnp.float32)
    y = proj.sketch(KEY, a, p, method="shgemm")
    omega = proj.gaussian(KEY, (n, p), dtype=jnp.bfloat16)
    want = proj.project(a, omega, method="shgemm")
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))


def test_rsvd_fused_accuracy_and_determinism():
    n, rank = 256, 24
    a = rsvd.matrix_with_singular_values(
        jax.random.PRNGKey(0), n, rsvd.singular_values_exp(n, rank, 1e-4))
    res1 = rsvd.rsvd(KEY, a, rank, method="shgemm_fused")
    res2 = rsvd.rsvd(KEY, a, rank, method="shgemm_fused")
    np.testing.assert_array_equal(np.asarray(res1.u), np.asarray(res2.u))
    err_fused = float(rsvd.reconstruction_error(a, res1))
    err_mat = float(rsvd.reconstruction_error(
        a, rsvd.rsvd(KEY, a, rank, method="shgemm")))
    # different Omega streams, same distribution: errors in the same decade
    assert err_fused < 3.0 * err_mat + 1e-6, (err_fused, err_mat)


def test_nystrom_fused():
    n, rank = 192, 16
    a = rsvd.matrix_with_singular_values(
        jax.random.PRNGKey(1), n, rsvd.singular_values_exp(n, rank, 1e-4))
    psd = np.asarray(a, np.float64)
    psd = jnp.asarray(psd @ psd.T, jnp.float32)
    u, lam = rsvd.nystrom_eigh(KEY, psd, rank, method="shgemm_fused")
    u32, lam32 = rsvd.nystrom_eigh(KEY, psd, rank, method="shgemm")
    np.testing.assert_allclose(np.asarray(lam), np.asarray(lam32),
                               rtol=0.1, atol=1e-4)


def test_hbm_bytes_model():
    """The whole point: fused HBM traffic is A+C alone (Omega bytes = 0)."""
    m, n, k = 8192, 512, 8192
    fused = kf.hbm_bytes_modeled(m, n, k, fused=True)
    mat = kf.hbm_bytes_modeled(m, n, k, fused=False)
    assert fused == m * k * 4 + m * n * 4
    assert mat - fused == k * n * 2  # exactly the Omega bf16 read traffic
