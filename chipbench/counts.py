"""Operations and bytes that a kernel or a model step needs, from shapes.

These are the yardstick for every roofline share and utilization the
benchmark prints, kept here so that a change to the program cannot change
how its work is counted.  Each counts the work the computation requires,
never what an implementation happens to do on top (a re-read, a padded
block, a slot computed and thrown away), so a share of a peak computed
from them cannot pass 100% for a sound measurement.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown kind
    is an error, not a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> float:
    """Least time the chip could take (the larger of the compute and the
    memory bound) over the time taken, in percent."""
    if seconds <= 0:
        raise ValueError(f"roofline of a kernel that took {seconds} s")
    bound = max(flops / peak["bf16_flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * bound / seconds


def sketch(m: int, n: int, p: int) -> tuple[int, int]:
    """Y = A (m x n, f32) @ Omega (n x p): 2mnp operations; bytes are A read
    once and Y written once, both f32.  Omega's bytes are not counted, so a
    method that makes Omega in the kernel and one that reads it are held to
    the same work."""
    return 2 * m * n * p, 4 * m * n + 4 * m * p


def kv_bytes_per_position(model: dict) -> int:
    """Bytes one cache position holds for one request: K and V of every
    layer and kv head, in the type the model is served in."""
    width = {"bfloat16": 2, "float16": 2, "float32": 4}[model["torch_dtype"]]
    return (2 * model["num_hidden_layers"] * model["num_key_value_heads"]
            * model["head_dim"] * width)


def dense_params_per_token(model: dict) -> int:
    """Multiply-adds per token of a dense GQA transformer's matmuls: the
    q, k, v and o projections and the gated MLP of every layer, and the
    LM head."""
    d, h, kv, hd, f = (model["hidden_size"], model["num_attention_heads"],
                       model["num_key_value_heads"], model["head_dim"],
                       model["intermediate_size"])
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return model["num_hidden_layers"] * per_layer + d * model["vocab_size"]


def model_flops(model: dict, context: int) -> int:
    """Operations the model requires for one position that attends over
    ``context`` positions (itself included): twice the matmul
    multiply-adds, and q.K^T and p.V over the context in every layer."""
    attn = (4 * model["num_hidden_layers"] * model["num_attention_heads"]
            * model["head_dim"] * context)
    return 2 * dense_params_per_token(model) + attn
