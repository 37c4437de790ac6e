"""Driver for ``kind: serve``: a dense GQA model served greedily through
the program's ``Scheduler`` over a ``ModelStep`` slot pool, on the host
clock.

Set-up makes the weights on the device from the seed in one jitted call,
in the type they are served in, builds the slot pool and compiles every
program the cell's traffic uses (each prefill chunk length, the batched
decode step and sampling).  It then starts the backlog: the queue is kept
full and the scheduler runs ``steady_steps`` steps, so that the window
opens on a pool that has turned over and not on an empty one.  The window
steps the same scheduler on, with the queue kept full, until ``--seconds``
have passed.

Every emitted token is stamped with the host clock as the scheduler
emits it, which is after the device has produced it (the scheduler reads
each token back to pick the next).  Each step of the window also records
how many slots hold a request, how many decode together, and how many
cache positions the held requests fill.  Once the window has closed and
the pool is freed, a sample of the requests finished in the window, drawn
from the seed, with the longest among them, is run through the plain
float32 reference (``references/transformer.py``); each served token is
held to how far its reference logit lies below the reference's best at its
position.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import counts, loadgen
from chipbench.references import transformer as ref

LAYER = ref.LAYER


def program_cfg(config: dict):
    """The program's ModelCfg for this configuration file: the registry's
    architecture with every size taken from the file."""
    from repro.configs.archs import ARCHS
    m, s = config["model"], config["serve"]
    return ARCHS[config["arch"]].with_(
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab=m["vocab_size"],
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        tie_embeddings=bool(m["tie_word_embeddings"]),
        param_dtype=m["torch_dtype"],
        use_flash_kernel=bool(s.get("use_flash_kernel", False)))


def weight_shapes(model: dict) -> dict[str, tuple[tuple, float]]:
    """name -> (shape, init std) of every weight, in the serving layout:
    layer leaves stacked over layers under ``layers/p0/``.  Each projection
    is N(0, 1/fan_in), so that every layer writes into the residual stream
    at the scale of what it reads and a served token depends on its context
    (with N(0, 0.02) everywhere the tied embedding of the current token
    decides the next one alone); the embedding is N(0, initializer_range).
    A std of 0 is a norm gain stored as an offset from one (so: one)."""
    d, h, kv, hd, f, v, n = (
        model["hidden_size"], model["num_attention_heads"],
        model["num_key_value_heads"], model["head_dim"],
        model["intermediate_size"], model["vocab_size"],
        model["num_hidden_layers"])
    s_d, s_o, s_f = d ** -0.5, (h * hd) ** -0.5, f ** -0.5
    layer = {
        "norm1/scale": ((d,), 0.0), "norm2/scale": ((d,), 0.0),
        "attn/wq": ((d, h, hd), s_d), "attn/wk": ((d, kv, hd), s_d),
        "attn/wv": ((d, kv, hd), s_d), "attn/wo": ((h * hd, d), s_o),
        "attn/q_norm": ((hd,), 0.0), "attn/k_norm": ((hd,), 0.0),
        "mlp/w_gate": ((d, f), s_d), "mlp/w_up": ((d, f), s_d),
        "mlp/w_down": ((f, d), s_f),
    }
    out = {"embed/tokens": ((v, d), float(model["initializer_range"])),
           "final_norm/scale": ((d,), 0.0)}
    out.update({LAYER + k: ((n,) + shape, s)
                for k, (shape, s) in layer.items()})
    return out


def make_weights(key, model: dict) -> dict:
    """Random weights from ``key``, made on the device in one jitted call,
    in the type they are served in."""
    shapes = weight_shapes(model)
    dtype = jnp.dtype(model["torch_dtype"])

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(shapes))
        out = {}
        for k, (name, (shape, std)) in zip(keys, sorted(shapes.items())):
            out[name] = (jnp.zeros(shape, dtype) if std == 0.0 else
                         (std * jax.random.normal(k, shape, jnp.float32)
                          ).astype(dtype))
        return out
    return build(key)


class _Clock:
    """The scheduler's metrics hook, stamping every token with the host
    clock (the scheduler passes its virtual time, which is ignored)."""

    def __init__(self):
        self.tokens: dict[int, list[float]] = {}

    def on_submit(self, rid, now, prompt_len, max_new):
        self.tokens[rid] = []

    def on_token(self, rid, now):
        self.tokens[rid].append(time.perf_counter())

    def on_reject(self, *args, **kw):
        pass

    on_admit = on_finish = sample = on_reject


class Driver:
    control = "fp8"           # the reference on fp8 weights (``readings``)

    def __init__(self, *, config: dict, traffic: dict, seed: int, spans,
                 trace: bool):
        if traffic["kind"] != "backlog":
            raise ValueError(f"serve driver takes backlog traffic, not "
                             f"{traffic['kind']!r}")
        self.cfg, self.traffic, self.seed = config, traffic, seed
        self.spans, self.trace = spans, trace
        self.model_dims = config["model"]
        self.serve = config["serve"]
        self.flops = 0            # model operations required in the window
        self.decode_batch = []    # slots in each batched decode step
        self.held = []            # (slots holding a request, positions) a step
        self._in_window = False

    # -- set-up --------------------------------------------------------------
    def setup(self) -> None:
        from repro.models import transformer as T
        self.pcfg = program_cfg(self.cfg)
        self.params = make_weights(
            jnp.asarray(loadgen.key_words(self.seed, 0)), self.model_dims)
        want = {k: (tuple(v.shape), jnp.dtype(v.dtype))
                for k, v in T.abstract_params(self.pcfg).items()}
        have = {k: (tuple(v.shape), jnp.dtype(v.dtype))
                for k, v in self.params.items()}
        if want != have:
            raise RuntimeError(
                f"the program takes other weights than the benchmark makes: "
                f"{sorted(set(want.items()) ^ set(have.items()))[:6]}")
        jax.block_until_ready(self.params)
        self.model = self._pool()
        self._warm()
        self._start()

    def _pool(self):
        from repro.serve.model_step import ModelStep
        s = self.serve
        model = ModelStep(self.pcfg, self.params, slots=int(s["slots"]),
                          max_seq=int(s["max_seq"]), temperature=0.0)
        self._wrap(model)
        return model

    def _wrap(self, model) -> None:
        """Host spans and work counts around the pool's calls into the
        model step (instance attributes shadow the methods)."""
        prefill, decode, sample = (model.prefill_rows, model.decode_logits,
                                   model.sample)
        m = self.model_dims

        def prefill_rows(slot, tokens, start):
            if self._in_window:
                self.flops += sum(counts.model_flops(m, start + i + 1)
                                  for i in range(len(tokens)))
            with self.spans("prefill_rows"):
                return prefill(slot, tokens, start)

        def decode_logits(tokens, write_pos, slot_mask=None):
            if self._in_window:
                live = int(np.sum(slot_mask)) if slot_mask is not None \
                    else model.slots
                self.flops += live * counts.model_flops(m, write_pos + 1)
                self.decode_batch.append(live)
            self.spans.begin("decode_step")     # closed by sample
            return decode(tokens, write_pos, slot_mask)

        def sample_(logits):
            out = sample(logits)
            self.spans.end("decode_step")
            return out

        model.prefill_rows, model.decode_logits = prefill_rows, decode_logits
        model.sample = sample_

    def _warm(self) -> None:
        """Every program the cell's traffic uses, at its shapes: each
        prefill chunk length, the masked decode step and sampling, and the
        scheduler's own eager operations."""
        model, s = self.model, self.serve
        chunk = int(s["prefill_chunk"])
        model.begin_slot(0)
        for n in range(1, chunk + 1):
            model.prefill_rows(0, [1] * n, 0)
        mask = np.zeros(model.slots, bool)
        mask[0] = True
        model.sample(model.decode_logits(
            np.ones((model.slots, 1), np.int32), chunk, slot_mask=mask))
        # two short requests through the scheduler, admitted together so
        # that one catches up with the other: the eager operations of
        # admission, catch-up and promotion, at no cost in window shapes
        sch = self._scheduler()
        for rid in range(2):
            sch.submit(rid, [1 + rid] * (chunk + 3 + 5 * rid), 3)
        while sch.queue or sch._live():
            sch.step()
        jax.block_until_ready(model.cache)
        self.spans.intervals.clear()

    def _scheduler(self):
        from repro.serve.scheduler import Scheduler
        s = self.serve
        return Scheduler(self.model, max_queue=int(s["max_queue"]),
                         prefill_chunk=int(s["prefill_chunk"]),
                         metrics=_Clock())

    def _start(self) -> None:
        """The backlog's first ``steady_steps`` scheduler steps, so that the
        window opens on a pool in its steady turnover."""
        self.reqs = loadgen.requests(self.traffic, self.seed,
                                     self.model_dims["vocab_size"],
                                     int(self.traffic["requests"]))
        self.sch = self._scheduler()
        self.clock = self.sch.metrics
        self.nxt = 0
        for _ in range(int(self.serve["steady_steps"])):
            self._step()
        jax.block_until_ready(self.model.cache)
        self.spans.intervals.clear()

    def _step(self) -> None:
        """Top the queue up to its depth, then one scheduler step."""
        sch, reqs = self.sch, self.reqs
        while len(sch.queue) < sch.max_queue and self.nxt < len(reqs):
            r = reqs[self.nxt]
            sch.submit(r.rid, r.prompt, r.max_new)
            self.nxt += 1
        if not (sch.queue or sch._live()):
            raise RuntimeError(f"traffic ran out after {len(reqs)} "
                               f"requests: raise 'requests'")
        with self.spans("step"):
            sch.step()

    # -- the window ------------------------------------------------------------
    def window(self, seconds: float) -> None:
        sch, model = self.sch, self.model
        self.done_before = len(sch.finished)
        self._in_window = True
        self.spans.begin("window")
        t0 = time.perf_counter()
        self.t0 = t0
        while time.perf_counter() - t0 < seconds:
            self._step()
            live = sch._live()
            self.held.append((len(live), sum(int(model.pos[s])
                                             for s in live)))
        self.t_close = time.perf_counter()
        self.window_s = self.t_close - t0
        self.spans.end("window")
        self._in_window = False

    # -- what the window produced ------------------------------------------------
    def _in(self, ts: list[float]) -> list[float]:
        return [t for t in ts if self.t0 <= t <= self.t_close]

    def token_times(self) -> list[float]:
        return [t for ts in self.clock.tokens.values() for t in self._in(ts)]

    def gaps_in_window(self) -> list[float]:
        """Every gap between consecutive output tokens of one request, both
        inside the window."""
        out = []
        for ts in self.clock.tokens.values():
            ts = self._in(ts)
            out.extend(b - a for a, b in zip(ts, ts[1:]))
        return out

    @property
    def attempted(self) -> int:
        """Requests served in the window: those with a token inside it."""
        return sum(1 for ts in self.clock.tokens.values() if self._in(ts))

    @property
    def failed(self) -> int:
        """Requests of the window evicted for want of context (the queue is
        only ever topped up to its depth, so none is rejected)."""
        return self.evicted

    def notes(self, tail_s: float) -> list[str]:
        """How full the pool was in the window, and the output rate in its
        last ``tail_s``: a traced run records only that tail, so this rate
        in a traced and an untraced run shows what the profiler costs."""
        s = self.serve
        slots, max_seq = int(s["slots"]), int(s["max_seq"])
        per_pos = counts.kv_bytes_per_position(self.model_dims)
        held = np.mean([h for h, _ in self.held])
        pos = np.mean([p for _, p in self.held])
        tail = [t for t in self.token_times()
                if t >= self.t_close - tail_s]
        return [
            f"serve: {held!r} of {slots} slots hold a request, "
            f"{np.mean(self.decode_batch)!r} decode together "
            f"(mean over {len(self.held)} steps)",
            f"serve: the held requests fill {pos!r} cache positions, "
            f"{pos * per_pos!r} B of KV, {100 * pos / (slots * max_seq)!r}% "
            f"of the pool",
            f"serve: {len(tail) / tail_s!r} tokens/s in the window's "
            f"last {tail_s} s"]

    # -- after the window -----------------------------------------------------
    def release(self) -> None:
        """Free the slot pool (cache, factors, sketches) before the
        reference runs; the benchmark's weights stay for it."""
        done = self.sch.finished[self.done_before:]
        self.evicted = sum(1 for r in done if r.evicted)
        self.finished = [(r.rid, list(r.prompt), list(r.out))
                         for r in done if not r.evicted]
        self.model = self.sch = None
        gc.collect()

    def sample_for_check(self) -> list[tuple[int, list, list]]:
        """Requests finished in the window, drawn from the seed, the longest
        first, until ``check_tokens`` served tokens are in the sample."""
        done = sorted(self.finished, key=lambda f: f[0])
        if not done:
            raise RuntimeError("no request finished in the window")
        rng = loadgen.rng_for(self.seed, 3)
        longest = max(done, key=lambda f: len(f[1]) + len(f[2]))
        rest = [done[i] for i in rng.permutation(len(done))
                if done[i] is not longest]
        target = int(self.serve["check_tokens"])
        out, n = [longest], len(longest[2])
        for f in rest:
            if n >= target:
                break
            out.append(f)
            n += len(f[2])
        return out

    def readings(self, control: str | None = None) -> dict[str, float]:
        """Widest gap below the reference's best of a served token over the
        sample.  With ``control="fp8"`` the reference on fp8 weights stands
        in the program's place: the gap of the token it puts first at the
        same positions."""
        dims = ref.Dims.of(self.model_dims)
        length = int(self.serve["max_seq"])
        params = self.params
        if control not in (None, "fp8"):
            raise ValueError(f"no control {control!r} for serving")
        ctl = ref.quantize_fp8(params) if control else None
        worst = 0.0
        for _, prompt, out in self.sample_for_check():
            seq = prompt + out[:-1]
            toks = np.zeros(length, np.int32)
            toks[:len(seq)] = seq
            served = np.zeros(length, np.int32)
            valid = np.zeros(length, bool)
            first = len(prompt) - 1
            served[first:first + len(out)] = out
            valid[first:first + len(out)] = True
            logits = ref.forward(params, jnp.asarray(toks), dims)
            if ctl is None:
                gap = ref.served_gap(logits, jnp.asarray(served),
                                     jnp.asarray(valid))
            else:
                gap = ref.first_choice_gap(
                    logits, ref.forward(ctl, jnp.asarray(toks), dims),
                    jnp.asarray(valid))
            worst = max(worst, float(gap))
        return {"served_gap": worst}

    def verify(self) -> list[tuple[str, float, float]]:
        got = self.readings()
        return [(k, got[k], float(v)) for k, v in self.cfg["limits"].items()]
