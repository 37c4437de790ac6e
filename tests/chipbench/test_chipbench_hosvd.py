"""The RP-HOSVD cell on the CPU at a tiny size: the library against the plain
reference on seeded Algorithm 3 tensors, a fault the comparison catches,
the projection the driver pins, the scopes of its program and the readers
of its per-layer metrics."""

from __future__ import annotations

import json
import math
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from chipbench import counts, loadgen, run, scopes
from chipbench.references import hosvd as ref
from chipbench.spans import Spans

ROOT = Path(__file__).resolve().parents[2]
TINY = json.loads((ROOT / "tests" / "chipbench" / "tiny" /
                   "hosvd.paper.json").read_text())
PAPER = json.loads((ROOT / "chipbench" / "configs" /
                    "paper-hosvd.json").read_text())
V5E = counts.peaks("TPU v5 lite")


def _reader(name):
    return run.load_module(ROOT / "chipbench" / "metrics" / f"{name}.py",
                           f"chipbench_metric_{name}")


def _driver(seed: int = 3):
    driver = run.load_module(ROOT / "chipbench" / "drivers" / "hosvd.py",
                             "chipbench_driver_hosvd").Driver(
        config=TINY["config"], traffic=TINY["traffic"], seed=seed,
        spans=Spans(), trace=False)
    driver.setup()
    return driver


def _result(root, capsys) -> dict:
    rc = run.main(["--workload", TINY["name"], "--seed", str(2 ** 40 + 9),
                   "--seconds", "1", "--trace", "0"], root=root,
                  require_chip=False)
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    return json.loads(out.out.strip().splitlines()[-1])


@pytest.mark.parametrize("seed", [5, 2 ** 33 + 1, 3150001601])
def test_library_matches_the_reference(seed):
    """``repro.core.hosvd.rp_hosvd`` with the pinned projection against
    plain Algorithm 2 on the same tensor and keys, under the tiny limits."""
    from repro.core import hosvd as program
    c = TINY["config"]
    ranks = tuple(c["ranks"])
    a = ref.test_tensor(jnp.asarray(loadgen.key_words(seed, 0)),
                        tuple(c["dims"]), ranks, c["pad"], c["noise"])
    key = jnp.asarray(loadgen.key_words(seed, 1000))
    got = program.rp_hosvd(key, a, ranks, method=c["method"], dist=c["dist"],
                           omega_dtype=getattr(jnp, c["omega_dtype"]))
    want = ref.rp_hosvd(key, a, ranks=ranks)
    assert got.core.shape == want[0].shape == ranks
    readings = ref.compare(a, got.core, got.factors, *want)
    assert set(readings) == set(c["limits"])
    for k, limit in c["limits"].items():
        assert float(readings[k]) <= limit, (k, float(readings[k]))


def test_core_altered(tiny_root, capsys, monkeypatch):
    """A core scaled by 1 + 1e-4 moves the reconstruction by about 1e-4 of
    the tensor: the run reads not correct."""
    from repro.core import hosvd as program
    real = program.rp_hosvd

    def altered(*a, **kw):
        core, factors = real(*a, **kw)
        return program.TuckerResult(core * (1 + 1e-4), factors)
    monkeypatch.setattr(program, "rp_hosvd", altered)
    result = _result(tiny_root, capsys)
    assert result["correct"] is False
    gap = result["checks"]["recon_gap"]
    assert gap["value"] > gap["limit"]


def test_hosvd_runs_the_projection_its_configuration_pins(
        tiny_root, capsys, monkeypatch):
    """The reference draws the Omegas of a pinned projection, so the driver
    passes the configuration's, never the library's default."""
    from repro.core import hosvd as program
    real, seen = program.rp_hosvd, set()

    def spy(*a, **kw):
        seen.add((kw.get("method"), kw.get("dist"), kw.get("omega_dtype"),
                  kw.get("ranks")))
        return real(*a, **kw)
    monkeypatch.setattr(program, "rp_hosvd", spy)
    result = _result(tiny_root, capsys)
    assert result["correct"] is True
    assert seen == {("shgemm", "gaussian", jnp.bfloat16, (8, 8, 8))}


def test_scoped_program_maps_every_line_of_algorithm_2():
    from repro import tracing
    driver = _driver()
    module, compiled, scope_of, calls = scopes.scoped_program(driver)
    assert module == "jit_rp_hosvd" and len(calls) == scopes.REPS
    assert set(scope_of.values()) == set(tracing.SCOPES["rp_hosvd"]) == {
        "hosvd.project", "hosvd.factor", "hosvd.core"}
    # the window's keys are never the scoped trace's
    assert all(not jnp.array_equal(args[0], driver._key(i))
               for i, args in enumerate(calls))


@pytest.mark.parametrize("config", [PAPER, TINY["config"]],
                         ids=["paper-hosvd", "tiny-hosvd"])
def test_projection_roofline_by_hand(config, monkeypatch):
    """Each mode's bound is the larger of 2 I_i P_i J_i operations at the
    bf16 peak and 4 I_i P_i + 4 I_i J_i bytes at the HBM peak, with P_i the
    product of the other modes; the share is their sum over the scope's
    time."""
    run_ = SimpleNamespace(trace=object(), config=config, peaks=V5E)
    monkeypatch.setitem(scopes._MEASURED, id(run_), {"hosvd.project": 0.9})
    total = math.prod(config["dims"])
    bound = 0.0
    for n, r in zip(config["dims"], config["ranks"]):
        p = total // n
        bound += max(2 * n * p * r / V5E["bf16_flops_per_s"],
                     (4 * n * p + 4 * n * r) / V5E["hbm_bytes_per_s"])
    share = _reader("project_roofline.hosvd").read(run_)
    assert share == pytest.approx(100 * bound / 0.9e-3, rel=1e-12)
    if config is PAPER:           # memory-bound: 67,141,632 B per mode
        assert counts.sketch(256, 65536, 32) == (1073741824, 67141632)
        assert share == pytest.approx(100 * 3 * 67141632 / 819e9 / 0.9e-3)


SCOPE_READERS = {"project_ms.hosvd": "hosvd.project",
                 "qr_ms.hosvd": "hosvd.factor", "core_ms.hosvd": "hosvd.core"}


@pytest.mark.parametrize("name", sorted(SCOPE_READERS))
def test_scope_readers_on_a_fake_run(name, monkeypatch):
    run_ = SimpleNamespace(trace=object())
    by_scope = {"hosvd.project": 0.9, "hosvd.factor": 0.4,
                "hosvd.core": 0.2, "": 0.01}
    monkeypatch.setitem(scopes._MEASURED, id(run_), by_scope)
    assert _reader(name).read(run_) == by_scope[SCOPE_READERS[name]]
    silent = SimpleNamespace(trace=object())      # a program with no scopes
    monkeypatch.setitem(scopes._MEASURED, id(silent), None)
    assert _reader(name).read(silent) is None
    assert _reader(name).read(SimpleNamespace(trace=None)) is None


def test_trace_readers_on_a_fake_trace():
    """``device_ms.hosvd`` divides the traced window's busy time by the
    ``hosvd`` spans begun in it; ``idle_share.hosvd`` is the rest of the
    window.  Without a trace, or without a call in it, both read nothing."""
    summary = SimpleNamespace(
        window_ns=(0, 10_000_000), busy_s=0.006, idle_share=0.4,
        spans={"hosvd": [(-1, 5), (100, 3_000_000), (3_000_100, 9_999_000),
                         (10_000_001, 10_000_002)]})
    run_ = SimpleNamespace(trace=summary)
    assert _reader("device_ms.hosvd").read(run_) == pytest.approx(3.0)
    assert _reader("idle_share.hosvd").read(run_) == pytest.approx(40.0)
    empty = SimpleNamespace(trace=SimpleNamespace(window_ns=(0, 1), spans={}))
    assert _reader("device_ms.hosvd").read(empty) is None
    for name in ("device_ms.hosvd", "idle_share.hosvd"):
        assert _reader(name).read(SimpleNamespace(trace=None)) is None


def test_reference_tensor_has_the_stated_rank_and_noise():
    """Algorithm 3's rank-(J_i - pad) maps give each unfolding J_i - pad
    singular values of the signal; the noise is ``noise`` of the norm."""
    c = TINY["config"]
    key = jax.random.PRNGKey(4)
    clean = ref.test_tensor(key, tuple(c["dims"]), tuple(c["ranks"]),
                            c["pad"], 0.0)
    noisy = ref.test_tensor(key, tuple(c["dims"]), tuple(c["ranks"]),
                            c["pad"], c["noise"])
    gap = float(jnp.linalg.norm(noisy - clean) / jnp.linalg.norm(clean))
    assert gap == pytest.approx(c["noise"], rel=1e-3)
    keep = c["ranks"][0] - c["pad"]
    for mode in range(3):
        s = jnp.linalg.svd(ref.unfold(clean, mode), compute_uv=False)
        assert float(s[keep - 1] / s[0]) > 1e-3
        assert float(s[keep] / s[0]) < 1e-5
