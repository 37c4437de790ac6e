"""Production serving launcher: continuous-batching scheduler over slots.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
        --requests 32 --arrival-rate 200 --slots 6 --report report.json

With ``--arrival-rate`` (or ``--load-trace``) the launcher drives the real
scheduler (serve/scheduler.py, DESIGN.md §15): seeded Poisson arrivals from
serve/loadgen.py (or a replayed trace file), bounded-queue admission,
chunked prefill interleaved with decode, and an SLO summary table
(TTFT/TPOT, p50/p99 latency, tokens/sec, queue depth) from serve/metrics.py
— written as JSON with ``--report``.  ``--save-trace`` stores the generated
trace for later byte-identical replays.

Compressed-attention serving (DESIGN.md §12): ``--kv-rank r`` maintains the
incremental per-slot KV sketches; adding ``--kv-compress-ratio x`` makes the
engine act on them — slots swap their dense prefix for rank-r factors every
``x * r`` rows and decode attends through the factors.  With ``--hbm-budget``
admission becomes compression-aware: concurrency is capped at what the
budget holds at worst case, so factored slots admit more streams.

Without a trace/rate the launcher falls back to the legacy closed-loop
Engine run (submit everything, drain)."""

from __future__ import annotations

import argparse
import json
import logging
import time

import jax

from repro._atomic_io import atomic_write_json
from repro.configs.base import smoke_config
from repro.launch.compile_cache import configure_compile_cache
from repro.models import registry as R
from repro.models import transformer as T
from repro.serve import loadgen
from repro.serve.engine import Engine, Request
from repro.serve.metrics import format_slo_table
from repro.serve.model_step import ModelStep
from repro.serve.scheduler import Scheduler

log = logging.getLogger("repro.launch.serve")


def _build(args, cfg):
    params = T.init_params(cfg, jax.random.PRNGKey(args.seed))
    kw = dict(slots=args.slots, max_seq=args.max_seq,
              temperature=args.temperature, kv_sketch_rank=args.kv_rank,
              kv_compress_ratio=args.kv_compress_ratio)
    return params, kw


def run_scheduler(args, cfg) -> None:
    """Open-loop run: trace arrivals through the scheduler, SLO table out."""
    params, kw = _build(args, cfg)
    model = ModelStep(cfg, params, **kw)
    sch = Scheduler(model, max_queue=args.max_queue,
                    prefill_chunk=args.prefill_chunk,
                    hbm_budget=args.hbm_budget)
    if args.load_trace:
        trace = loadgen.load_trace(args.load_trace)
        log.info("replaying %d requests from %s", len(trace),
                 args.load_trace)
    else:
        trace = loadgen.generate_trace(args.seed, args.requests,
                                       args.arrival_rate, vocab=cfg.vocab)
        log.info("generated trace: %d requests at %.1f req/s (seed %d)",
                 len(trace), args.arrival_rate, args.seed)
    if args.save_trace:
        loadgen.save_trace(trace, args.save_trace,
                           meta={"seed": args.seed, "arch": cfg.name,
                                 "arrival_rate": args.arrival_rate})
        log.info("trace saved to %s (replay with --load-trace)",
                 args.save_trace)
    t0 = time.perf_counter()
    sch.run(trace)
    wall = time.perf_counter() - t0
    summary = sch.metrics.summary(expected=len(trace))
    log.info("drained in %.2fs wall; admission cap %d streams "
             "(stream bound %d B%s)", wall, sch.max_streams,
             sch.stream_bound,
             f", budget {args.hbm_budget} B" if args.hbm_budget else "")
    print("SLO summary (virtual-clock):")
    print(format_slo_table(summary))
    if args.report:
        atomic_write_json(args.report, {
            "config": {"arch": cfg.name, "slots": args.slots,
                       "max_seq": args.max_seq,
                       "kv_rank": args.kv_rank,
                       "kv_compress_ratio": args.kv_compress_ratio,
                       "hbm_budget": args.hbm_budget,
                       "max_streams": sch.max_streams,
                       "prefill_chunk": args.prefill_chunk,
                       "max_queue": args.max_queue},
            "wall_s": wall, "summary": summary})
        log.info("report written to %s", args.report)


def run_engine(args, cfg) -> None:
    """Legacy closed-loop Engine run (no arrivals: submit all, drain)."""
    params, kw = _build(args, cfg)
    eng = Engine(cfg, params, max_queue=args.max_queue, **kw)
    rng = jax.random.PRNGKey(args.seed + 1)
    for i in range(args.requests):
        rng, k = jax.random.split(rng)
        prompt = [int(t) for t in
                  jax.random.randint(k, (4,), 0, cfg.vocab)]
        eng.submit(Request(rid=i, prompt=prompt, max_new=args.max_new))

    t0 = time.perf_counter()
    steps = 0
    while eng.queue or any(eng.active):
        n = eng.step()
        steps += 1
        if steps % 10 == 0:
            log.info("step %d: %d active, %d queued", steps, n,
                     len(eng.queue))
    dt = time.perf_counter() - t0
    total = args.requests * args.max_new
    log.info("served %d requests / %d tokens in %.2fs (%.1f tok/s)",
             args.requests, total, dt, total / dt)
    if eng.kv_fact is not None:
        rep = eng.kv_bytes_report()
        comp = [r for r in rep["slots"] if r["comp_len"] > 0]
        log.info("kv compression: %d/%d slots factored, per-slot HBM "
                 "%d B vs dense %d B (%.2fx)", len(comp), eng.slots,
                 comp[0]["compressed_bytes"] if comp else 0,
                 comp[0]["dense_bytes"] if comp else 0,
                 (comp[0]["compressed_bytes"] / comp[0]["dense_bytes"])
                 if comp else 1.0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=sorted(R.ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv-rank", type=int, default=None,
                    help="maintain incremental per-slot KV sketches at this "
                         "rank (serve/kv_compress.py)")
    ap.add_argument("--kv-compress-ratio", type=float, default=None,
                    help="act on the sketches: swap a slot's dense prefix "
                         "for rank-r factors every ratio*rank rows "
                         "(requires --kv-rank)")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="open-loop load: generate a seeded Poisson trace "
                         "at this req/s and drive the scheduler")
    ap.add_argument("--load-trace", default=None,
                    help="replay a trace file saved by --save-trace "
                         "(overrides --arrival-rate/--requests)")
    ap.add_argument("--save-trace", default=None,
                    help="save the generated trace for later replay")
    ap.add_argument("--report", default=None,
                    help="write the SLO summary as JSON here")
    ap.add_argument("--max-queue", type=int, default=1024,
                    help="bounded request queue: past this depth submits "
                         "are rejected loudly (backpressure)")
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="prefill/catch-up token budget per scheduler step")
    ap.add_argument("--hbm-budget", type=int, default=None,
                    help="swappable-KV byte budget for compression-aware "
                         "admission (caps concurrent streams)")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    configure_compile_cache()
    cfg = R.get_arch(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    if args.load_trace or args.arrival_rate is not None:
        run_scheduler(args, cfg)
    else:
        run_engine(args, cfg)


if __name__ == "__main__":
    main()
