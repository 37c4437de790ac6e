"""Fixtures for the chip benchmark's tests: a checkout holding the real
harness with tiny configurations, so that a whole run fits the CPU."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY_MODEL = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "rope_theta": 1000000, "rms_norm_eps": 1e-06,
    "max_position_embeddings": 128, "tie_word_embeddings": True,
    "torch_dtype": "bfloat16", "hidden_act": "silu",
    "initializer_range": 0.02,
}
TINY_SERVE = {"slots": 4, "max_seq": 128, "prefill_chunk": 8,
              "max_queue": 8, "check_tokens": 48, "steady_steps": 6}
TINY_CONFIGS = {
    "tiny-rsvd": {"kind": "rsvd", "source": "tiny", "n": 256, "rank": 16,
                  "oversample": 10, "power_iters": 0, "s_p": 1e-4,
                  "method": "shgemm", "dist": "gaussian",
                  "omega_dtype": "bfloat16",
                  "limits": {"residual_ratio": 1.1, "sv_gap": 1e-5,
                             "u_orth": 3e-6}},
    "tiny-dense": {"kind": "serve", "source": "tiny", "arch": "qwen3-0.6b",
                   "model": TINY_MODEL, "serve": TINY_SERVE,
                   "limits": {"served_gap": 0.015}},
}
LENGTHS = {"prompt_len": {"median": 20, "sigma": 0.8, "min": 4, "max": 60},
           "output_len": {"median": 8, "sigma": 0.8, "min": 2, "max": 24}}
TINY_TRAFFIC = {
    "tiny-loop": {"kind": "closed_loop", "callers": 1, "resident": 2,
                  "sample": 3, "sketch_reps": 2},
    "tiny-backlog": dict(kind="backlog", block=8, requests=400, **LENGTHS),
}
TINY_CELLS = [
    ("tiny.rsvd", "tiny-rsvd", "tiny-loop"),
    ("tiny.batch", "tiny-dense", "tiny-backlog"),
]


def make_tiny_root(dest: Path) -> Path:
    """A checkout with the real harness and BENCHMARK.json's metrics, whose
    cells are tiny."""
    shutil.copytree(ROOT / "chipbench", dest / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name, cfg in TINY_CONFIGS.items():
        (dest / "chipbench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
    for name, tr in TINY_TRAFFIC.items():
        (dest / "chipbench" / "traffic" / f"{name}.json").write_text(
            json.dumps(tr))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    real = {"rsvd.paper": "tiny.rsvd", "serve.qwen3.batch": "tiny.batch"}
    manifest["workloads"] = [
        {"name": n, "config": c, "traffic": t, "chips": 1, "why": "tiny"}
        for n, c, t in TINY_CELLS]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [real[w] for w in m["workloads"]]
    (dest / "BENCHMARK.json").write_text(json.dumps(manifest))
    return dest


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    return make_tiny_root(tmp_path_factory.mktemp("checkout"))
