"""A whole run on the CPU at a tiny size, with the timed path broken
underneath, comes out not correct: once for each fault a cell can have.
An answer altered where it is produced (rSVD: the singular values the
program returns; serving: the token the sampler returns), and a step that
returns its state unchanged (serving: a decode step whose cache writes are
dropped).  Half a batch left out and a missing exchange between chips are
faults of training and of several chips; no cell here has them."""

from __future__ import annotations

import json

from chipbench import run


def _result(root, cell, capsys) -> dict:
    rc = run.main(["--workload", cell, "--seed", "7", "--seconds", "2",
                   "--trace", "0"], root=root, require_chip=False)
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    return json.loads(out.out.strip().splitlines()[-1])


def test_sound_runs_are_correct(tiny_root, tiny_cell, capsys):
    assert _result(tiny_root, tiny_cell["name"], capsys)["correct"] is True


def test_rsvd_answer_altered(tiny_root, capsys, monkeypatch):
    from repro.core import rsvd as program
    real = program.rsvd

    def altered(*a, **kw):
        u, s, vt = real(*a, **kw)
        return program.SVDResult(u, s.at[0].multiply(1 + 1e-4), vt)
    monkeypatch.setattr(program, "rsvd", altered)
    result = _result(tiny_root, "tiny.rsvd", capsys)
    assert result["correct"] is False
    assert result["checks"]["sv_gap"]["value"] > \
        result["checks"]["sv_gap"]["limit"]


def test_served_token_altered(tiny_root, capsys, monkeypatch):
    from repro.serve.model_step import ModelStep
    real = ModelStep.sample
    monkeypatch.setattr(ModelStep, "sample", lambda self, logits: (
        real(self, logits) + 1) % self.cfg.vocab)
    assert _result(tiny_root, "tiny.batch", capsys)["correct"] is False


def test_decode_step_returns_its_state_unchanged(tiny_root, capsys,
                                                  monkeypatch):
    from repro.serve.model_step import ModelStep
    real = ModelStep.decode_logits

    def frozen(self, tokens, write_pos, slot_mask=None):
        cache = self.cache
        out = real(self, tokens, write_pos, slot_mask)
        self.cache = cache
        return out
    monkeypatch.setattr(ModelStep, "decode_logits", frozen)
    assert _result(tiny_root, "tiny.batch", capsys)["correct"] is False
