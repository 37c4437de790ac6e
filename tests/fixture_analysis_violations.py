"""Seeded-violation fixtures for the contract checker (tests/test_analysis.py).

One deliberate violation of each analysis rule, used to prove the passes
fire on exactly the patterns they claim to catch.  This module is NOT in
the CI lint scope (the analysis job lints ``src`` and ``benchmarks``) —
do not "fix" these.
"""

import json
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


# --- JAX-NO-GEMM: an "SRHT-style" structured apply that cheats with a GEMM
def bad_srht_apply(key, a, p=4):
    signs = jnp.where(jax.random.bernoulli(key, 0.5, (a.shape[1],)), 1.0,
                      -1.0)
    omega = jnp.eye(a.shape[1], int(p)) * signs[:, None]
    return jnp.dot(a, omega)          # the contract says adds/gathers only


# --- JAX-DTYPE-CAST: f16 cast on the A path (bf16-mode contract)
def bad_a_downcast(a, omega):
    return jnp.dot(a.astype(jnp.float16), omega.astype(jnp.bfloat16)
                   .astype(jnp.float32).astype(jnp.bfloat16))


# --- JAX-UNKEYED: randomness seeded inside the traced program
def bad_unkeyed(x):
    return x + jax.random.normal(jax.random.PRNGKey(0), x.shape)


# --- PL-WRITE-ALIAS: every parallel grid step writes output block (0, 0)
def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def bad_alias_kernel(x):
    return pl.pallas_call(
        _copy_kernel,
        grid=(2, 2),
        in_specs=[pl.BlockSpec((8, 8), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((8, 8), lambda i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, 8), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=True,
    )(x)


# --- LINT-ATOMIC-IO: non-atomic checkpoint/bench artifact write
def bad_ckpt_write(path, doc):
    with open(path, "w") as f:
        json.dump(doc, f)


# --- LINT-NP-RANDOM: global-state numpy randomness
def bad_np_random(n):
    return np.random.rand(n)


# --- LINT-WALLCLOCK: wall clock used for a duration
def bad_duration():
    t0 = time.time()
    return time.time() - t0


# --- LINT-INT-TRACER: bare concretization inside a jit boundary
@jax.jit
def bad_int_tracer(x):
    return x + int(x[0])
