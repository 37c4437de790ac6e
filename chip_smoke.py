"""Smoke run of the system's main path on a TPU, through its normal entry
points, at full size.

    python chip_smoke.py               # one chip: rSVD, RP-HOSVD, qwen3-0.6b
    python chip_smoke.py --four-chips  # four chips: distributed rSVD only

One chip:

* rSVD at the paper's §5.1 shape (n=4096, rank 256, oversample 10) with the
  f32 reference and the ``shgemm``, ``shgemm_pallas`` and ``shgemm_fused``
  projections; both Pallas programs must contain the compiled kernel.
* RP-HOSVD and RP-ST-HOSVD at the paper's §5.2 shape (256^3, ranks 32).
* qwen3-0.6b at published widths (28 layers, d_model 1024, vocab 151936,
  random weights from a seed) served by ``Scheduler`` over ``ModelStep``,
  dense and then with rank-128 KV compression through the factored-decode
  kernel, each checked against a float32 reference.

Four chips: ``distributed_rsvd`` on a 2x2 mesh against single-chip ``rsvd``.

Every phase prints what it measured; any failed check raises, so the script
exits non-zero.  The last line of a passing run is one JSON object naming
the device.  With no TPU the script exits non-zero before any phase runs.
All phases run in this one process: a chip belongs to one process at a time.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.archs import QWEN3_0_6B  # noqa: E402
from repro.configs.paper_randnla import PAPER_HOSVD, PAPER_RSVD  # noqa: E402
from repro.core import distributed, hosvd, rsvd  # noqa: E402
from repro.core import projection as proj  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serve import loadgen  # noqa: E402
from repro.serve.model_step import ModelStep  # noqa: E402
from repro.serve.scheduler import Scheduler  # noqa: E402

# The paper's claim is accuracy at parity with single precision: a mixed-
# precision residual may exceed the f32 residual on the same Omega by 10%.
PARITY = 1.1
# Served logits (bf16 weights, activations and KV cache: 8 significant bits,
# unit roundoff 2^-9 = 0.2%) against the float32 reference: 1% of the largest
# reference logit, 5x the unit roundoff.  At qwen3-0.6b widths the CPU
# measured 0.08-0.28% for 1-16 layers.
LOGIT_RTOL = 0.01
# Compressed against dense serving with rank = head_dim, where every swap is
# exact but for rounding: the factors, and the order of the sums, through a
# bf16 residual stream (on a TPU the decode kernel's f32 factor GEMMs also
# run at default precision, one bf16 pass).  So the runs agree below bf16
# resolution (DESIGN.md §12): one bf16 unit roundoff, 2^-9, of the largest
# dense logit.  On one v5e the sound path read 0.73-0.77 against ~2.0 and a
# rank-64 swap 5.0-5.8; a rank-127 swap (0.86-0.95) passes, so the check
# catches gross faults only (PERF.md §6).  tests/test_engine_compress.py's
# 1e-1 is this bound at smoke widths, where logits are small; here they
# reach ~1000.
COMPRESS_RTOL = 2.0 ** -9
# HOSVD on an exactly low-rank tensor sits at the f32 rounding floor, where
# the two-term bf16 split cannot match f32 (it keeps ~16 of A's 24 bits: 5-9x
# the f32 residual on the CPU); the parity rule is applied to the same tensor
# plus seeded noise of this relative size.
HOSVD_NOISE = 1e-3
# Distributed against single-chip singular values, same Omega: TSQR and the
# sharded sums change only f32 rounding (4 shards, n=4096), so they agree to
# far below 1e-4 of the largest singular value.
DIST_SV_ATOL = 1e-4

OVERSAMPLE = PAPER_RSVD.oversample
# (method, Omega storage): fp16 runs on the chip through XLA only (DESIGN.md
# §2); the Pallas methods store Omega in bf16.
RSVD_RUNS = (("f32", jnp.bfloat16), ("shgemm", jnp.bfloat16),
             ("shgemm", jnp.float16), ("shgemm_pallas", jnp.bfloat16),
             ("shgemm_fused", jnp.bfloat16))
HOSVD_METHODS = ("f32", "shgemm", "shgemm3", "shgemm_fused")
PALLAS_METHODS = ("shgemm_pallas", "shgemm_fused")


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)


def _hdot(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _omega(key, shape, method: str, dtype=jnp.bfloat16) -> jax.Array:
    """The Omega ``method`` draws from ``key``: the fused kernel hashes the
    counter lattice, every other method draws jax.random."""
    if method == "shgemm_fused":
        return proj.fused_omega(key, shape, dtype=dtype)
    return proj.materialize_omega(key, shape, dtype=dtype)


@functools.partial(jax.jit, static_argnames=("rank", "method", "dtype"))
def _plain_rsvd(key, a, rank: int, method: str,
                dtype=jnp.bfloat16) -> rsvd.SVDResult:
    """Paper Algorithm 1 in plain f32 on the Omega ``method`` draws: the
    reference its residual is held to (a different draw alone moves an
    rSVD residual by up to 15% at n=1024)."""
    omega = _omega(key, (a.shape[1], rank + OVERSAMPLE), method, dtype)
    q, _ = jnp.linalg.qr(_hdot(a, omega.astype(jnp.float32)))
    u_b, s, vt = jnp.linalg.svd(_hdot(q.T, a), full_matrices=False)
    return rsvd.SVDResult(_hdot(q, u_b)[:, :rank], s[:rank], vt[:rank])


@functools.partial(jax.jit,
                   static_argnames=("ranks", "method", "sequential"))
def _plain_tucker(key, t, ranks: tuple, method: str,
                  sequential: bool) -> hosvd.TuckerResult:
    """Paper Algorithm 2 (``sequential``: its ST variant) in plain f32 on
    the per-mode Omegas ``method`` draws."""
    keys = jax.random.split(key, t.ndim)
    core, factors = t, []
    for i in range(t.ndim):
        unf = hosvd.unfold(core if sequential else t, i)
        omega = _omega(keys[i], (unf.shape[1], ranks[i]), method)
        q, _ = jnp.linalg.qr(_hdot(unf, omega.astype(jnp.float32)))
        factors.append(q)
        if sequential:
            core = hosvd.mode_dot(core, q.T, i)
    if not sequential:
        for i, q in enumerate(factors):
            core = hosvd.mode_dot(core, q.T, i)
    return hosvd.TuckerResult(core, tuple(factors))


def _paper_matrix(n: int, rank: int, s_p: float, seed: int) -> jax.Array:
    return rsvd.matrix_with_singular_values(
        jax.random.PRNGKey(seed), n, rsvd.singular_values_exp(n, rank, s_p))


def rsvd_phase(*, n: int, rank: int, s_p: float, seed: int) -> dict:
    """rSVD per projection method against plain f32 on the same Omega."""
    a = _paper_matrix(n, rank, s_p, seed)
    key = jax.random.PRNGKey(seed + 1)
    out = {}
    for m, dtype in RSVD_RUNS:
        name = f"{m} {jnp.dtype(dtype).name}"
        res = rsvd.rsvd(key, a, rank, oversample=OVERSAMPLE, method=m,
                        omega_dtype=dtype)
        err = float(rsvd.reconstruction_error(a, res))
        base = float(rsvd.reconstruction_error(
            a, _plain_rsvd(key, a, rank, m, dtype)))
        out[name] = err
        log(f"rsvd n={n} rank={rank} {name}: residual {err:.6e}, plain f32 "
            f"on the same Omega {base:.6e}, ratio {err / base:.5f}")
        check(np.isfinite(err) and err <= PARITY * base,
              f"rsvd {name}: residual {err:.6e} > {PARITY} x f32 {base:.6e}")
    if not ops.resolve_interpret(None):      # the kernels run compiled
        for m in PALLAS_METHODS:
            hlo = rsvd.rsvd.lower(key, a, rank, oversample=OVERSAMPLE,
                                  method=m).compile().as_text()
            check("tpu_custom_call" in hlo,
                  f"rsvd {m}: compiled program holds no Pallas kernel")
            log(f"rsvd {m}: compiled program holds tpu_custom_call")
    return out


def hosvd_phase(*, dims: tuple, ranks: tuple, pad: int, seed: int) -> dict:
    """RP-HOSVD and RP-ST-HOSVD per method against plain f32 on the same
    Omegas: on the paper's Algorithm 3 tensor (recovery checked, ratio
    reported) and on it plus ``HOSVD_NOISE`` (parity checked)."""
    clean = hosvd.make_test_tensor(jax.random.PRNGKey(seed), dims, ranks, pad)
    noise = jax.random.normal(jax.random.PRNGKey(seed + 1), dims, jnp.float32)
    noisy = clean + (HOSVD_NOISE * jnp.linalg.norm(clean)
                     / jnp.linalg.norm(noise)) * noise
    key = jax.random.PRNGKey(seed + 2)
    out = {}
    for tname, t in (("algorithm-3", clean), ("algorithm-3+noise", noisy)):
        for fn, seq in ((hosvd.rp_hosvd, False), (hosvd.rp_sthosvd, True)):
            for m in HOSVD_METHODS:
                err = float(hosvd.reconstruction_error(
                    t, fn(key, t, ranks, method=m)))
                base = float(hosvd.reconstruction_error(
                    t, _plain_tucker(key, t, ranks, m, seq)))
                out[(fn.__name__, tname, m)] = err
                log(f"{fn.__name__} {tname} {m}: residual {err:.6e}, plain "
                    f"f32 on the same Omega {base:.6e}, ratio "
                    f"{err / base:.5f}")
                check(np.isfinite(err), f"{fn.__name__} {m}: not finite")
                if tname == "algorithm-3":
                    # rank-(J-pad) recovery at the rounding floor
                    # (tests/test_hosvd_lstsq.py's bound)
                    check(err < 1e-4, f"{fn.__name__} {m}: {err:.3e} >= 1e-4")
                else:
                    check(err <= PARITY * base,
                          f"{fn.__name__} {tname} {m}: residual {err:.6e} "
                          f"> {PARITY} x f32 {base:.6e}")
    return out


class _WatchedStep(ModelStep):
    """ModelStep that keeps the logits row each slot computes at
    ``watch_pos`` — the request's first decode position — and how it was
    computed (catch-up prefill step, or batched decode, factored or not)."""

    def __init__(self, *args, watch_pos: int, **kw):
        super().__init__(*args, **kw)
        self.watch_pos = watch_pos
        self.rows: dict[int, np.ndarray] = {}
        self.via: dict[int, str] = {}
        self.decode_spec = None

    def prefill_rows(self, slot, tokens, start):
        logits = super().prefill_rows(slot, tokens, start)
        if start + len(tokens) - 1 == self.watch_pos:
            self.rows[slot] = np.asarray(logits)
            self.via[slot] = "catch-up prefill"
        return logits

    def decode_logits(self, tokens, write_pos, slot_mask=None):
        comp = self._kv_comp_len.copy()
        logits = super().decode_logits(tokens, write_pos, slot_mask)
        self.decode_spec = (tokens, write_pos, slot_mask)
        if write_pos == self.watch_pos:
            for s in np.flatnonzero(slot_mask):
                self.rows[int(s)] = np.asarray(logits[s])
                self.via[int(s)] = ("batched decode, factored prefix "
                                    f"{int(comp[s])}" if comp[s]
                                    else "batched decode")
        return logits

    def decode_hlo(self) -> str:
        """StableHLO of the last batched decode step, lowered again from
        its shapes (no second compile)."""
        tokens, write_pos, slot_mask = self.decode_spec
        batch = {"tokens": jnp.asarray(tokens), "cache": self.cache,
                 "write_pos": jnp.asarray(write_pos, jnp.int32)}
        if self.kv_fact is not None:
            batch["kv_factors"] = self.kv_fact
            batch["comp_len"] = jnp.asarray(self._kv_comp_len)
        return self._decode_masked.lower(self.params, batch,
                                         jnp.asarray(slot_mask)).as_text()


def _peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} B"


def _serve(cfg, params, trace, *, slots: int, max_seq: int, **kw):
    """Serve ``trace`` the way launch/serve.py does: Scheduler over a
    ModelStep slot pool, greedy."""
    watch = len(trace[0].prompt)
    model = _WatchedStep(cfg, params, slots=slots, max_seq=max_seq,
                         temperature=0.0, watch_pos=watch, **kw)
    sch = Scheduler(model, max_queue=len(trace))
    t0 = time.perf_counter()
    sch.run(trace)
    wall = time.perf_counter() - t0
    done = sorted(sch.finished, key=lambda r: r.rid)
    check(len(done) == len(trace) and not any(r.evicted for r in done),
          f"served {len(done)} of {len(trace)} requests")
    check(all(len(r.out) == r.max_new for r in done),
          "a request ended short of max_new")
    check(set(model.rows) == {r.slot for r in done},
          f"first decode logits seen for slots {sorted(model.rows)}")
    return model, done, wall


def serve_phase(cfg, *, slots: int, max_seq: int, prompt_len: int,
                max_new: int, n_requests: int, kv_rank: int,
                seed: int) -> dict:
    """Dense then compressed serving; each request's first decode logits
    against a float32 full forward, then compressed against dense."""
    params = T.init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    trace = [loadgen.TraceRequest(
        rid=i, arrival_s=0.0, max_new=max_new,
        prompt=[int(x) for x in rng.integers(0, cfg.vocab, prompt_len)])
        for i in range(n_requests)]

    model, done, wall = _serve(cfg, params, trace, slots=slots,
                               max_seq=max_seq)
    log(f"serve dense {cfg.name}: {len(done)} requests x {max_new} tokens "
        f"(prompts {prompt_len}) in {wall:.1f} s host wall, compilation "
        f"included; peak device memory {_peak_bytes()}")
    dense = {r.rid: model.rows[r.slot] for r in done}
    first = {r.rid: r.out[0] for r in done}
    for r in done:
        log(f"serve dense rid {r.rid}: first decode via {model.via[r.slot]}")
    del model
    gc.collect()     # free the dense slot pool before the compressed one

    cfg32 = cfg.with_(activation_dtype="float32")

    @jax.jit
    def reference(p, toks):
        return T.forward(cfg32, p, toks).logits[0, -1].astype(jnp.float32)

    out = {}
    for r in done:
        toks = jnp.asarray([r.prompt + [r.out[0]]], jnp.int32)
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(reference(params, toks))
        diff = float(np.max(np.abs(dense[r.rid] - ref)))
        scale = float(np.max(np.abs(ref)))
        same = int(np.argmax(dense[r.rid])) == int(np.argmax(ref))
        out[("dense", r.rid)] = diff
        log(f"serve dense rid {r.rid}: max |logit - f32 reference| {diff:.6f}"
            f" of max |reference| {scale:.4f} ({diff / scale:.3e}); greedy "
            f"token agrees: {same}")
        check(np.isfinite(diff) and diff <= LOGIT_RTOL * scale,
              f"rid {r.rid}: logit diff {diff} > {LOGIT_RTOL} x {scale}")

    ccfg = cfg.with_(use_flash_kernel=True)
    model, done_c, wall = _serve(ccfg, params, trace, slots=slots,
                                 max_seq=max_seq, kv_sketch_rank=kv_rank,
                                 kv_compress_ratio=1.0)
    log(f"serve compressed {cfg.name} rank {kv_rank}: {len(done_c)} requests"
        f" in {wall:.1f} s host wall, compilation included; comp_len per "
        f"slot {model._kv_comp_len.tolist()}; peak device memory "
        f"{_peak_bytes()}")
    factored = [s for s, v in model.via.items() if "factored" in v]
    check(bool(factored), "no first decode attended through the factors")
    if not ops.resolve_interpret(None):      # the kernel runs compiled
        check("tpu_custom_call" in model.decode_hlo(),
              "compressed decode step holds no Pallas kernel")
        log("serve compressed: decode step holds tpu_custom_call")
    for r in done_c:
        diff = float(np.max(np.abs(model.rows[r.slot] - dense[r.rid])))
        bound = COMPRESS_RTOL * float(np.max(np.abs(dense[r.rid])))
        out[("compressed", r.rid)] = diff
        log(f"serve compressed rid {r.rid}: first decode via "
            f"{model.via[r.slot]}; max |logit - dense| {diff:.6f}, bound "
            f"{bound:.6f}; first token agrees: {r.out[0] == first[r.rid]}")
        check(np.isfinite(diff) and diff <= bound,
              f"rid {r.rid}: compressed vs dense {diff} > {bound}")
        check(r.out[0] == first[r.rid],
              f"rid {r.rid}: first token differs from the dense run")
    return out


def distributed_phase(*, n: int, rank: int, s_p: float, seed: int) -> dict:
    """distributed_rsvd on a 2x2 (data, model) mesh over four devices
    against single-device rsvd on the same key."""
    devices = set(jax.devices())
    mesh = make_mesh((2, 2), ("data", "model"))
    a = _paper_matrix(n, rank, s_p, seed)
    a_sh = distributed.shard_matrix(a, mesh)
    check({s.device for s in a_sh.addressable_shards} == devices,
          "A's shards do not cover every device")
    key = jax.random.PRNGKey(seed + 1)
    out = {}
    for m in ("shgemm", "shgemm_fused"):
        res = distributed.distributed_rsvd(key, a_sh, rank, mesh,
                                           oversample=OVERSAMPLE, method=m)
        one = rsvd.rsvd(key, a, rank, oversample=OVERSAMPLE, method=m)
        check({s.device for s in res.u.addressable_shards} == devices,
              f"{m}: U's shards do not cover every device")
        sv = float(jnp.max(jnp.abs(res.s - one.s)) / one.s[0])
        e_d = float(rsvd.reconstruction_error(a, rsvd.SVDResult(
            res.u, res.s, res.vt)))
        e_1 = float(rsvd.reconstruction_error(a, one))
        out[m] = sv
        log(f"distributed_rsvd {m} mesh {dict(mesh.shape)}: max |sigma - "
            f"single-device sigma| / sigma_1 {sv:.3e}; residual {e_d:.6e} "
            f"(single device {e_1:.6e})")
        check(sv <= DIST_SV_ATOL, f"{m}: singular values differ by {sv}")
        check(e_d <= PARITY * e_1, f"{m}: residual {e_d} > {PARITY} x {e_1}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only distributed rSVD on a 2x2 mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if args.four_chips and len(devices) != 4:
        print(f"chip_smoke: --four-chips needs 4 chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    log(f"compile cache: {configure_compile_cache()}")
    log(f"device: {dev.platform} {dev.device_kind} x {len(devices)}")

    cfg = PAPER_RSVD
    if args.four_chips:
        distributed_phase(n=cfg.n, rank=cfg.rank, s_p=cfg.s_p, seed=args.seed)
    else:
        t0 = time.perf_counter()
        rsvd_phase(n=cfg.n, rank=cfg.rank, s_p=cfg.s_p, seed=args.seed)
        log(f"phase rsvd: {time.perf_counter() - t0:.1f} s host wall")
        t0 = time.perf_counter()
        hosvd_phase(dims=PAPER_HOSVD.dims, ranks=PAPER_HOSVD.ranks,
                    pad=PAPER_HOSVD.pad, seed=args.seed)
        log(f"phase hosvd: {time.perf_counter() - t0:.1f} s host wall")
        t0 = time.perf_counter()
        serve_phase(QWEN3_0_6B, slots=4, max_seq=2048, prompt_len=160,
                    max_new=16, n_requests=4, kv_rank=QWEN3_0_6B.head_dim,
                    seed=args.seed)
        log(f"phase serve: {time.perf_counter() - t0:.1f} s host wall")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
