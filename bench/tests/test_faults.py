"""A run with the timed path broken underneath comes out not correct:
once for each fault the cells can have (the harness's look for a chip is
skipped; everything else runs as on the chip, at the dummy size)."""
import jax.numpy as jnp
import pytest

from conftest import run_cell


def test_answer_altered_where_produced(dummy_root, capsys, monkeypatch):
    """Graph cells: the captured executable's logits come out altered."""
    from repro.core.capture import CapturedGraph

    orig = CapturedGraph.__call__

    def altered(self, inputs):
        outs = orig(self, inputs)
        return [o.at[..., 0].add(5.0) if o.ndim == 3 and o.shape[-1] > 100
                else o for o in outs]

    monkeypatch.setattr(CapturedGraph, "__call__", altered)
    rc, line = run_cell(dummy_root, "dummy.graph", capsys=capsys)
    assert rc == 0 and line["correct"] is False
    assert line["check"]["logit_err"]["value"] > line["check"]["logit_err"]["limit"]


def test_token_altered_where_produced(dummy_root, capsys, monkeypatch):
    """Serve cells: the sampler returns a token other than the greedy one."""
    from repro.serving import engine

    orig = engine.sample_token

    def altered(logits, rng, temperature=0.0, **kw):
        return (orig(logits, rng, temperature, **kw) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "sample_token", altered)
    rc, line = run_cell(dummy_root, "dummy.serve", capsys=capsys)
    assert rc == 0 and line["correct"] is False


def test_step_returns_its_state_unchanged(dummy_root, capsys, monkeypatch):
    """Serve cells: the paged decode step hands back the KV pages it was
    given, so no decoded token's keys and values are ever stored."""
    from repro.serving import engine

    orig = engine._cached_paged_decode_fn

    def stale(model):
        fn = orig(model)

        def step(p, c, t, bt, pos):
            logits, _ = fn(p, c, t, bt, pos)
            return logits, c
        return step

    monkeypatch.setattr(engine, "_cached_paged_decode_fn", stale)
    rc, line = run_cell(dummy_root, "dummy.serve", capsys=capsys)
    assert rc == 0 and line["correct"] is False
