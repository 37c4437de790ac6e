"""Compile rehearsal: the main path's Pallas kernels, compiled for a TPU v5e
that is described, not attached.

Interpret mode hides what only the chip's compiler refuses: casts Mosaic has
no lowering for, block shapes off the (8, 128) tiling, fast-memory budgets.
Each test compiles one kernel at the shapes the chip runs (the paper's rSVD
sketch, qwen3-0.6b decode and prefill attention) and asserts the compiled
program holds the kernel (``tpu_custom_call``).  The serving pool's prefill
program is compiled too, to show that it keeps the pool in place.  Nothing
runs.

The topology is described inside a module fixture and never at import: only
one process may load the TPU compiler's library at a time, so under several
test workers only the worker given this file may touch it.  Keep every such
compile in this one file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.archs import ARCHS
from repro.core import projection as proj
from repro.kernels import ops
from repro.kernels import shgemm as _k
from repro.models import cache as cache_mod
from repro.models import transformer as T
from repro.serve.model_step import make_prefill_chunk

# PAPER_RSVD sketch: n = 4096, p_hat = rank 256 + oversample 10
N, P_HAT = 4096, 266
# qwen3-0.6b serving: 4 slots, max_seq 2048, 8 kv heads of 128, GQA 2:1,
# factor rank 128 (= head_dim)
SLOTS, SEQ, KV, HD, G, RANK = 4, 2048, 8, 128, 2, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory placed on one described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache off around each test."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("terms", [2, 3])
def test_shgemm_pallas_bf16_paper_rsvd(spec, terms):
    hlo = _compiled_text(
        lambda a, b: ops.shgemm(a, b, terms=terms, interpret=False),
        spec((N, N), jnp.float32), spec((N, P_HAT), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dist,omega_dtype", [
    ("gaussian", jnp.bfloat16),
    ("very_sparse", jnp.float8_e4m3fn),
])
def test_shgemm_fused_paper_rsvd(spec, dist, omega_dtype):
    hlo = _compiled_text(
        lambda a, key: ops.shgemm_fused(a, key, P_HAT, dist=dist,
                                        omega_dtype=omega_dtype,
                                        interpret=False),
        spec((N, N), jnp.float32), spec((2,), jnp.uint32))
    assert "tpu_custom_call" in hlo


def test_factored_decode_qwen3_widths(spec):
    bf16, f32 = jnp.bfloat16, jnp.float32
    hlo = _compiled_text(
        lambda *xs: ops.factored_decode_attention(*xs, scale=HD ** -0.5,
                                                  interpret=False),
        spec((SLOTS, 1, KV * G, HD), bf16),
        spec((SLOTS, SEQ, KV, HD), bf16), spec((SLOTS, SEQ, KV, HD), bf16),
        spec((SLOTS, KV, SEQ, RANK), f32), spec((SLOTS, KV, RANK, HD), f32),
        spec((SLOTS, KV, SEQ, RANK), f32), spec((SLOTS, KV, RANK, HD), f32),
        spec((SLOTS,), jnp.int32), spec((), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_qwen3_widths(spec, dtype):
    hlo = _compiled_text(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                            interpret=False),
        spec((1, SEQ, KV * G, HD), dtype), spec((1, SEQ, KV, HD), dtype),
        spec((1, SEQ, KV, HD), dtype))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("method", ["shgemm", "shgemm3"])
def test_xla_split_keeps_its_rounding(spec, method):
    """The XLA split methods round A's high term with ``reduce_precision``
    (core/splitting._round_to), which survives TPU compilation; an
    f32 -> bf16 -> f32 round trip in its place left the low term zero on the
    chip and the method a single bf16 pass."""
    hlo = _compiled_text(lambda a, b: proj.project(a, b, method=method),
                         spec((N, N), jnp.float32),
                         spec((N, P_HAT), jnp.bfloat16))
    assert "reduce-precision" in hlo


def test_fp16_omega_refused_by_kernels_compiled_by_xla(spec):
    """The fp16 decision (DESIGN.md §2): Mosaic cannot load an f16 B tile,
    so the compiled Pallas wrappers refuse fp16 and name the way out, while
    the XLA ``shgemm`` method compiles the paper's fp16 path."""
    a = spec((N, N), jnp.float32)
    b16 = spec((N, P_HAT), jnp.float16)
    with pytest.raises(Exception, match="Invalid vector type"):
        _compiled_text(lambda a, b: _k.shgemm_pallas(a, b), a,
                       spec((N, 512), jnp.float16))
    with pytest.raises(ValueError, match="bfloat16.*method='shgemm'"):
        _compiled_text(lambda a, b: ops.shgemm(a, b, interpret=False), a, b16)
    with pytest.raises(ValueError, match="bfloat16.*method='shgemm'"):
        _compiled_text(
            lambda a, key: ops.shgemm_fused(a, key, P_HAT,
                                            omega_dtype=jnp.float16,
                                            interpret=False),
            a, spec((2,), jnp.uint32))
    hlo = _compiled_text(lambda a, b: proj.project(a, b, method="shgemm"),
                         a, b16)
    assert "tpu_custom_call" not in hlo


def test_prefill_chunk_keeps_the_pool_in_place(spec):
    """The serving cell's prefill program (qwen3-0.6b widths, a 4 x 1024
    bf16 pool, chunk 8; two layers suffice) aliases every cache leaf to its
    output and selects over no pool-sized array: it writes the slot's rows
    into the donated pool instead of merging a whole new pool.  Nor does it
    hold all layers of the slot at once: each layer reads its rows from the
    pool and only the chunk's rows come back."""
    slots, seq, chunk = 4, 1024, 8
    cfg = ARCHS["qwen3-0.6b"].with_(n_layers=2, param_dtype="bfloat16")
    params = {k: spec(v.shape, v.dtype)
              for k, v in T.abstract_params(cfg).items()}
    cache = cache_mod.build_cache(cfg, slots, seq, make=spec)
    i32 = spec((), jnp.int32)
    program, _ = make_prefill_chunk(cfg)
    hlo = program.lower(
        params, cache, spec((chunk,), jnp.int32), i32, i32
    ).compile().as_text()
    n_cache = len(jax.tree.leaves(cache))
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry_", hlo)
    assert aliased and aliased.group(1).count("may-alias") == n_cache
    pool = {"bf16[" + ",".join(map(str, x.shape)) + "]"
            for x in jax.tree.leaves(cache)}
    assert pool == {f"bf16[2,{slots},{seq},{KV},{HD}]"}
    selects = [ln for ln in hlo.splitlines()
               if " select(" in ln and any(p in ln for p in pool)]
    assert not selects, selects[:2]
    assert f"bf16[2,1,{seq},{KV},{HD}]" not in hlo
