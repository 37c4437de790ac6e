"""chip_smoke.py off the chip: it refuses to run without a TPU, and each of
its phases runs end to end here at a small size, in interpret mode.  The
sizes the chip runs are in the script; these tests keep its control flow,
checks and tolerances honest between chip runs."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro.configs.archs import QWEN3_0_6B
from repro.configs.base import smoke_config

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(script: Path, cwd: Path, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


def _no_result(proc) -> bool:
    lines = proc.stdout.strip().splitlines()
    return not lines or not lines[-1].lstrip().startswith("{")


def test_exits_nonzero_without_tpu():
    proc = _run(SCRIPT, ROOT)
    assert proc.returncode != 0
    assert _no_result(proc), proc.stdout
    assert "no TPU" in proc.stderr


def test_exits_nonzero_alone(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    proc = _run(lone, tmp_path)
    assert proc.returncode != 0
    assert _no_result(proc), proc.stdout


def test_rsvd_phase_small():
    out = _load().rsvd_phase(n=256, rank=16, s_p=1e-4, seed=0)
    assert set(out) == {"f32 bfloat16", "shgemm bfloat16", "shgemm float16",
                        "shgemm_pallas bfloat16", "shgemm_fused bfloat16"}


def test_hosvd_phase_small():
    out = _load().hosvd_phase(dims=(32, 32, 32), ranks=(10, 10, 10), pad=2,
                              seed=0)
    assert len(out) == 2 * 2 * 4


def test_serve_phase_small():
    cfg = smoke_config(QWEN3_0_6B)
    out = _load().serve_phase(cfg, slots=4, max_seq=64, prompt_len=16,
                              max_new=4, n_requests=4, kv_rank=cfg.head_dim,
                              seed=0)
    assert len(out) == 8


def test_distributed_phase_small():
    code = textwrap.dedent(f"""
        import importlib.util, sys
        spec = importlib.util.spec_from_file_location("chip_smoke",
                                                      {str(SCRIPT)!r})
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out = mod.distributed_phase(n=256, rank=16, s_p=1e-4, seed=0)
        print(sorted(out))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "['shgemm', 'shgemm_fused']" in proc.stdout
