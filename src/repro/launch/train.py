"""Production training launcher.

Builds a mesh over the available devices, applies the framework's sharding
rules + auto-layout, and runs the fault-tolerant training loop (resume,
retry, emergency-save, straggler watch).  On a real TPU pod slice this is
the per-host entrypoint (jax.distributed.initialize is called when the
environment provides coordinator info); on CPU it runs the same code on the
host device(s).

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --smoke --steps 50 --optimizer adamw --ckpt-dir /tmp/run1
"""

from __future__ import annotations

import argparse
import logging
import os

import jax
import numpy as np

from repro.configs.base import smoke_config
from repro.data.pipeline import MemmapTokens, SyntheticLM
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import registry as R
from repro.models import transformer as T
from repro.optim import compression
from repro.sharding import activation as act_sharding
from repro.sharding import rules
from repro.train.loop import LoopConfig, train

log = logging.getLogger("repro.launch.train")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b",
                    choices=sorted(R.ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor", "sgd"])
    ap.add_argument("--micro-batches", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--data", default=None,
                    help="token .bin file (np.int32); default synthetic")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")

    if "JAX_COORDINATOR" in os.environ:  # multi-host pod slice
        jax.distributed.initialize()
    configure_compile_cache()

    cfg = R.get_arch(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)

    mesh = make_host_mesh(args.model_parallel)
    act_sharding.set_mesh(mesh, tp=rules.tp_enabled(cfg)
                          and mesh.shape["model"] > 1)
    log.info("mesh %s | arch %s (%.1fM params) | tp=%s",
             dict(mesh.shape), cfg.name, T.param_count(cfg) / 1e6,
             rules.tp_enabled(cfg))

    params = T.init_params(cfg, jax.random.PRNGKey(args.seed))
    params = rules.shard_params(cfg, mesh, params)
    step_maker = R.make_train_step(cfg, optimizer=args.optimizer, lr=args.lr,
                                   micro_batches=args.micro_batches)
    opt_state = step_maker.init_opt(params)
    step = jax.jit(step_maker)

    host_id = jax.process_index()
    n_hosts = jax.process_count()
    if args.data:
        data = MemmapTokens(args.data, seq_len=args.seq,
                            global_batch=args.global_batch,
                            host_id=host_id, num_hosts=n_hosts)
    else:
        data = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq,
                           global_batch=args.global_batch,
                           host_id=host_id, num_hosts=n_hosts,
                           seed=args.seed)

    lcfg = LoopConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                      ckpt_dir=args.ckpt_dir)
    params, opt_state, hist = train(step, params, opt_state, data, lcfg)
    if hist:
        med = float(np.median([h["dt"] for h in hist]))
        toks = args.global_batch * args.seq / med
        log.info("done: loss %.4f -> %.4f | %.3fs/step | %.0f tok/s",
                 hist[0]["loss"], hist[-1]["loss"], med, toks)


if __name__ == "__main__":
    main()
