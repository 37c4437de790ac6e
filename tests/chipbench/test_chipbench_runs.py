"""Whole runs of the harness on the CPU at tiny sizes: each cell's tiny
counterpart comes out correct with its metrics and nothing compiled inside
the window, and a run with no chip, or without the program, prints no
result."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import run

ROOT = Path(__file__).resolve().parents[2]
CELLS = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]


def run_cell(root: Path, cell: str, capsys, seed: int = 2 ** 40 + 3,
             seconds: float = 2.0, **kw) -> tuple[dict, str]:
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", "0"], root=root,
                  require_chip=False, **kw)
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    return json.loads(out.out.strip().splitlines()[-1]), out.err


def test_tiny_cell_is_correct(tiny_root, tiny_cell, capsys):
    cell = tiny_cell["name"]
    manifest = json.loads((tiny_root / "BENCHMARK.json").read_text())
    e2e = {m["name"] for m in manifest["end_to_end"]
           if cell in m.get("workloads", [cell])}
    assert "setup_s" in e2e and len(e2e) >= 2
    result, err = run_cell(tiny_root, cell, capsys)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == e2e
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert "run: 0 program(s) compiled or loaded inside the window" in err
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    last = err.strip().splitlines()[-len(result["checks"]):]
    assert all(line.startswith("check ") for line in last)


def test_serving_window_opens_on_a_turned_over_pool(tiny_root, capsys):
    """Set-up runs the backlog, so the window's first steps already decode
    several slots and fill the pool; the run reports how full it was."""
    seen = {}
    result, err = run_cell(tiny_root, "tiny.batch", capsys,
                           driver_hook=lambda d: seen.update(
                               held=d.sch._live(), done=len(d.sch.finished)))
    assert result["correct"] is True
    assert len(seen["held"]) == 4 and seen["done"] > 0
    notes = [line for line in err.splitlines() if line.startswith("serve:")]
    assert len(notes) == 3
    assert "of 4 slots hold a request" in notes[0]
    assert "tokens/s in the window's last 10.0 s" in notes[2]


def test_rsvd_runs_the_projection_its_configuration_pins(
        tiny_root, capsys, monkeypatch):
    """The reference draws the Omega of a pinned projection, so the driver
    passes the configuration's, never the library's default."""
    import jax.numpy as jnp
    from repro.core import rsvd as program
    real, seen = program.rsvd, set()

    def spy(*a, **kw):
        seen.add((kw.get("method"), kw.get("dist"), kw.get("omega_dtype")))
        return real(*a, **kw)
    monkeypatch.setattr(program, "rsvd", spy)
    result, _ = run_cell(tiny_root, "tiny.rsvd", capsys, seconds=1.0)
    assert result["correct"] is True
    assert seen == {("shgemm", "gaussian", jnp.bfloat16)}


def test_no_chip_no_result(capsys):
    rc = run.main(["--workload", "rsvd.paper", "--seed", "1", "--seconds",
                   "1"])
    out = capsys.readouterr()
    assert rc == 1 and out.out == ""
    assert "needs 1 TPU chip" in out.err


@pytest.mark.parametrize("cell", CELLS, ids=[w["name"] for w in CELLS])
def test_command_from_the_checkout_finds_its_files(cell):
    """The benchmark's command, run as the driver runs it (from the root,
    with no path set), loads the cell's driver and then stops at the look
    for a chip."""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", cell["name"],
         "--seed", "1", "--seconds", "1"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1 and proc.stdout == "", proc.stderr[-2000:]
    assert f"needs {cell['chips']} TPU chip" in proc.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "rsvd.paper",
         "--seed", "1", "--seconds", "1"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout == ""
