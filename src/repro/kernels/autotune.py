"""Block-size autotuner for the SHGEMM + decode kernels, persistent JSON cache.

Replaces the hardcoded ``_pick_blocks`` heuristic: candidate ``(bm, bn, bk)``
tilings are filtered by the kernel's VMEM budget (``shgemm.vmem_bytes``, now
dtype- and variant-aware), timed through the same jit entry points the
benchmark harness uses, and the winner is cached in a JSON file keyed by
``(backend, M, N, K, dtype, terms, variant)`` so the sweep runs once per
problem shape per machine.  The factored-decode-attention kernel
(``kernels/factored_decode.py``) shares the cache through its own key space
(``<backend>:fdec:...`` -> ``block_kv``).

Entry points per kernel family:

  * ``pick_blocks`` / ``pick_decode_block`` — cheap, called by the ``ops``
    wrappers on every untuned call: cache hit returns the tuned blocks, miss
    falls back to the shrink-to-fit heuristic without timing anything.
  * ``autotune_blocks`` / ``autotune_decode_block`` — run the sweep on a
    cache miss and persist the winner; the benchmark harness (and anyone who
    cares about the last 20%) calls this once per shape.  A second
    invocation is a cache hit and skips re-timing entirely.

Timing-mode tagging (the interpret-poisoning fix): every entry records the
``mode`` it was timed under — ``"interpret"`` (Python evaluation of the
kernel body; all this container can produce) or ``"compiled"`` (real
backend).  Interpret-mode wall times say nothing about MXU/VMEM behavior,
so ``pick_*`` refuse to serve an ``interpret``-timed (or legacy untagged)
entry to a compiled run and fall back to the heuristic instead; interpret
runs accept any entry (block choice is accuracy-neutral there).  A shipped
default cache (``autotune_default.json`` next to this module, entries
tagged ``mode: "shipped"``) seeds common rSVD and decode shapes for real
backends until hardware timings land; the user's JSON file is consulted
first so real autotune results override the shipped defaults.

Cache location: ``$REPRO_AUTOTUNE_CACHE`` or ``~/.cache/repro/autotune.json``.
"""

from __future__ import annotations

import json
import os
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro._atomic_io import atomic_write_json
from repro.kernels import shgemm as _k

# Sweep space: MXU-aligned tilings from one (128, 128, 128) tile up to the
# deep-K shapes EXPERIMENTS.md's hillclimb explored.  Kept small on purpose —
# the sweep reruns per shape and each candidate costs a compile.
CANDIDATES: tuple[tuple[int, int, int], ...] = (
    (128, 128, 128),
    (128, 128, 256),
    (128, 256, 256),
    (256, 128, 256),
    (256, 256, 256),
    (256, 256, 512),
    (256, 512, 512),
    (512, 256, 512),
    (512, 512, 512),
)

VMEM_LIMIT = 16 * 2**20
VMEM_BUDGET_FRACTION = 0.8  # headroom for pipeline overheads / semaphores


def cache_path() -> str:
    return os.environ.get(
        "REPRO_AUTOTUNE_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "repro",
                     "autotune.json"))


# (path, mtime_ns, size) -> parsed cache.  pick_blocks runs on every untuned
# eager ops call (block resolution is outside the jit boundary so tuning can
# take effect mid-process), so re-parse only when the file actually changed.
_cache_memo: dict = {}


def _load_cache(path: str) -> dict:
    try:
        st = os.stat(path)
        memo_key = (path, st.st_mtime_ns, st.st_size)
        if memo_key not in _cache_memo:
            _cache_memo.clear()
            with open(path) as f:
                _cache_memo[memo_key] = json.load(f)
        return _cache_memo[memo_key]
    except (OSError, ValueError):
        return {}


def default_cache_path() -> str:
    """The shipped default cache (checked into the package): curated
    entries for common rSVD and decode shapes, tagged ``mode: "shipped"``."""
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "autotune_default.json")


_shipped_memo: dict = {}


def _load_shipped() -> dict:
    if "cache" not in _shipped_memo:
        try:
            with open(default_cache_path()) as f:
                _shipped_memo["cache"] = json.load(f)
        except (OSError, ValueError):
            _shipped_memo["cache"] = {}
    return _shipped_memo["cache"]


def _lookup(key: str, mode: str) -> dict | None:
    """User cache first (real autotune results override shipped defaults),
    then the shipped cache; unusable entries (see ``_entry_usable``) are
    passed over rather than served."""
    for cache in (_load_cache(cache_path()), _load_shipped()):
        hit = cache.get(key)
        if hit and _entry_usable(hit, mode):
            return hit
    return None


def _save_cache(path: str, cache: dict) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    atomic_write_json(path, cache, sort_keys=True)


def cache_key(m: int, n: int, k: int, b_dtype, terms: int,
              fused: bool, backend: str | None = None) -> str:
    backend = backend or jax.default_backend()
    variant = "fused" if fused else "mat"
    return f"{backend}:{m}x{n}x{k}:{jnp.dtype(b_dtype).name}:t{terms}:{variant}"


def decode_cache_key(s: int, g: int, hd: int, r: int,
                     backend: str | None = None) -> str:
    """Key space for the factored-decode kernel: the tunable is the kv block
    along the (padded) cache length ``s``; ``g``/``hd``/``r`` fix the
    per-block GEMM shapes."""
    backend = backend or jax.default_backend()
    return f"{backend}:fdec:s{s}:g{g}:hd{hd}:r{r}"


def timing_mode(interpret: bool | None = None) -> str:
    """The mode a timing run (or the current pick) executes under — the
    ``ops`` dispatch rule (``ops.resolve_interpret``)."""
    from repro.kernels.ops import resolve_interpret  # deferred: ops imports us
    return "interpret" if resolve_interpret(interpret) else "compiled"


def _entry_usable(entry: dict, mode: str) -> bool:
    """An interpret run may serve any entry (block choice is accuracy-
    neutral and wall-time-irrelevant there); a compiled run must not trust
    interpret-mode timings — or legacy untagged entries, which might be —
    and only accepts ``compiled`` winners or curated ``shipped`` defaults."""
    if mode == "interpret":
        return True
    return entry.get("mode") in ("compiled", "shipped")


def _round_up(x: int, align: int) -> int:
    return ((x + align - 1) // align) * align


def heuristic_blocks(m: int, n: int, k: int) -> tuple[int, int, int]:
    """Shrink default blocks for small problems (the old ``_pick_blocks``:
    128-aligned where the dims allow; tiny dims round up to 8/128)."""
    def shrink(dim, default, align):
        if dim >= default:
            return default
        return min(default, max(align, _round_up(dim, align)))
    bm = shrink(m, _k.DEFAULT_BM, 8)
    bn = shrink(n, _k.DEFAULT_BN, 128)
    bk = shrink(k, _k.DEFAULT_BK, 128)
    return bm, bn, bk


def candidate_blocks(m: int, n: int, k: int, *, b_dtype=jnp.bfloat16,
                     fused: bool = False,
                     vmem_budget: int | None = None) -> list[tuple[int, int, int]]:
    """CANDIDATES filtered to fit the VMEM budget and not exceed the padded
    problem (a block larger than the rounded-up dim only adds pad FLOPs)."""
    budget = vmem_budget or int(VMEM_LIMIT * VMEM_BUDGET_FRACTION)
    out = []
    for bm, bn, bk in CANDIDATES:
        if bm > max(_round_up(m, 8), 128):
            continue
        if bn > _round_up(n, 128) or bk > _round_up(k, 128):
            continue
        if _k.vmem_bytes(bm, bn, bk, b_dtype, fused=fused) > budget:
            continue
        out.append((bm, bn, bk))
    return out or [heuristic_blocks(m, n, k)]


def _median_time_us(fn: Callable[[], jax.Array], repeat: int = 3) -> float:
    """Median wall time (us) post-warmup — same protocol as the benchmark
    harness's ``time_jit`` (duplicated here: ``benchmarks/`` is not on the
    library path)."""
    jax.block_until_ready(fn())
    times = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def _default_time_fn(m: int, n: int, k: int, blocks: tuple[int, int, int],
                     b_dtype, terms: int, fused: bool) -> float:
    from repro.kernels import ops  # deferred: ops imports this module
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (m, k), jnp.float32)
    if fused:
        return _median_time_us(lambda: ops.shgemm_fused(
            a, key, n, blocks=blocks, terms=terms, omega_dtype=b_dtype))
    b = jax.random.normal(jax.random.fold_in(key, 1), (k, n),
                          jnp.float32).astype(b_dtype)
    return _median_time_us(lambda: ops.shgemm(a, b, blocks=blocks,
                                              terms=terms))


def pick_blocks(m: int, n: int, k: int, *, b_dtype=jnp.bfloat16,
                terms: int = 2, fused: bool = False,
                interpret: bool | None = None) -> tuple[int, int, int]:
    """Tuned blocks if this shape was ever autotuned on this backend (or is
    covered by the shipped defaults), else the shrink-to-fit heuristic.
    Never times anything.  ``interpret`` is the mode the caller will run the
    kernel in (``ops`` passes its resolved flag): a compiled run refuses
    interpret-timed winners rather than serving a poisoned entry."""
    mode = timing_mode(interpret)
    hit = _lookup(cache_key(m, n, k, b_dtype, terms, fused), mode)
    if hit:
        return tuple(hit["blocks"])
    return heuristic_blocks(m, n, k)


# --------------------------------------------------------------------------
# Factored-decode kernel block space (kernels/factored_decode.py)
# --------------------------------------------------------------------------

DECODE_CANDIDATES: tuple[int, ...] = (128, 256, 512)


def heuristic_decode_block(s: int) -> int:
    """Shrink-to-fit kv block for an untuned decode shape: one 256-wide
    block per kv chunk, or a single block covering short caches."""
    if s >= 256:
        return 256
    return max(8, _round_up(s, 8))


def candidate_decode_blocks(s: int) -> list[int]:
    out = [b for b in DECODE_CANDIDATES if b <= _round_up(s, 128)]
    return out or [heuristic_decode_block(s)]


def pick_decode_block(s: int, g: int, hd: int, r: int, *,
                      interpret: bool | None = None) -> int:
    """Tuned ``block_kv`` for the factored-decode kernel, else the
    heuristic; same mode gating as ``pick_blocks``.  A tuned block wider
    than the (rounded-up) cache is clamped — padding whole extra blocks
    only adds masked work."""
    mode = timing_mode(interpret)
    hit = _lookup(decode_cache_key(s, g, hd, r), mode)
    if hit:
        return min(int(hit["block_kv"]), max(8, _round_up(s, 8)))
    return heuristic_decode_block(s)


def _default_decode_time_fn(s: int, g: int, hd: int, r: int,
                            block_kv: int) -> float:
    from repro.kernels import ops  # deferred: ops imports this module
    key = jax.random.PRNGKey(0)
    kvh, b = 2, 2
    ks = jax.random.split(key, 7)
    mk = lambda k_, sh: jax.random.normal(k_, sh, jnp.float32)  # noqa: E731
    q = mk(ks[0], (b, 1, g * kvh, hd))
    k = mk(ks[1], (b, s, kvh, hd))
    v = mk(ks[2], (b, s, kvh, hd))
    k_us = mk(ks[3], (b, kvh, s, r))
    k_vt = mk(ks[4], (b, kvh, r, hd))
    v_us = mk(ks[5], (b, kvh, s, r))
    v_vt = mk(ks[6], (b, kvh, r, hd))
    comp = jnp.full((b,), s // 2, jnp.int32)
    return _median_time_us(lambda: ops.factored_decode_attention(
        q, k, v, k_us, k_vt, v_us, v_vt, comp, write_pos=s - 1,
        scale=hd ** -0.5, block_kv=block_kv))


def autotune_decode_block(s: int, g: int, hd: int, r: int, *,
                          candidates: Sequence[int] | None = None,
                          time_fn: Callable[..., float] | None = None,
                          cache_file: str | None = None,
                          force: bool = False,
                          interpret: bool | None = None) -> tuple[int, bool]:
    """Sweep kv blocks for one decode shape; returns ``(block_kv,
    from_cache)``.  ``time_fn(s, g, hd, r, block_kv) -> us`` is injectable
    for tests.  The persisted entry carries the timing ``mode`` and
    platform so ``pick_decode_block`` can refuse it on a real backend."""
    path = cache_file or cache_path()
    ckey = decode_cache_key(s, g, hd, r)
    cache = _load_cache(path)
    if not force and ckey in cache:
        return int(cache[ckey]["block_kv"]), True

    cands = (list(candidates) if candidates is not None
             else candidate_decode_blocks(s))
    timer = time_fn or _default_decode_time_fn
    timings = {blk: timer(s, g, hd, r, blk) for blk in cands}
    best = min(timings, key=timings.get)
    cache = dict(_load_cache(path))
    cache[ckey] = {
        "block_kv": best,
        "us": timings[best],
        "mode": timing_mode(interpret),
        "platform": jax.default_backend(),
        "swept": {str(blk): round(t, 2) for blk, t in sorted(timings.items())},
    }
    _save_cache(path, cache)
    return best, False


def autotune_blocks(m: int, n: int, k: int, *, b_dtype=jnp.bfloat16,
                    terms: int = 2, fused: bool = False,
                    candidates: Sequence[tuple[int, int, int]] | None = None,
                    time_fn: Callable[..., float] | None = None,
                    cache_file: str | None = None, force: bool = False,
                    interpret: bool | None = None
                    ) -> tuple[tuple[int, int, int], bool]:
    """Sweep candidate blocks for one problem shape; returns
    ``(blocks, from_cache)``.

    ``time_fn(m, n, k, blocks, b_dtype, terms, fused) -> us`` is injectable
    for tests; the default times the real ``ops`` entry point.  The
    persisted entry is tagged with the timing ``mode``/platform
    (``interpret`` defaults to the backend dispatch rule) so compiled runs
    never consume interpret-mode winners.
    """
    path = cache_file or cache_path()
    ckey = cache_key(m, n, k, b_dtype, terms, fused)
    cache = _load_cache(path)
    if not force and ckey in cache:
        return tuple(cache[ckey]["blocks"]), True

    cands = list(candidates) if candidates is not None else candidate_blocks(
        m, n, k, b_dtype=b_dtype, fused=fused)
    timer = time_fn or _default_time_fn
    timings = {}
    for blocks in cands:
        timings[blocks] = timer(m, n, k, blocks, b_dtype, terms, fused)
    best = min(timings, key=timings.get)
    # re-read (another process may have written) and copy (the loader memoizes
    # the parsed dict — don't mutate the shared object before the save lands)
    cache = dict(_load_cache(path))
    cache[ckey] = {
        "blocks": list(best),
        "us": timings[best],
        "mode": timing_mode(interpret),
        "platform": jax.default_backend(),
        "swept": {"x".join(map(str, c)): round(t, 2)
                  for c, t in sorted(timings.items())},
    }
    _save_cache(path, cache)
    return best, False
