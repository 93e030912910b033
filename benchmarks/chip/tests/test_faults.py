"""With the timed path broken underneath, ``correct`` comes out false:
a token altered where it is produced, and a decode step that leaves its
KV state unchanged."""
import jax
import pytest

import harness
from conftest import tiny_cell


def altered_token(te):
    for eng in te.stages:
        real = eng.decode_fused

        def decode_fused(*a, real=real, vocab=eng.cfg.vocab_size, **k):
            tt, gt, ct = real(*a, **k)
            return (tt + 1) % vocab, gt, ct

        eng.decode_fused = decode_fused


def stale_state(te):
    from repro.models import model as M
    for eng in te.stages:
        cfg = eng.cfg

        def fused_decode(params, tokens, cache, positions, active, fold,
                         k, cfg=cfg):
            out = M.decode_fused_steps(params, cfg, tokens, cache,
                                       positions, active, fold, k=k)
            return out[:4] + (cache,) + out[5:]

        fn = jax.jit(fused_decode, static_argnames=("k",))
        eng._get_fused = lambda mode, beta, fn=fn: fn


@pytest.mark.parametrize("fault", [altered_token, stale_state])
def test_fault_is_not_correct(fault, quiet):
    cell, cfgs = tiny_cell("cascade-chat")
    out = harness.run_cell(cell, 424242, 1.5, model_configs=cfgs,
                           fault=fault, say=quiet)
    assert out["correct"] is False
    worst = max(c["value"] / c["limit"] for c in out["checks"].values()
                if c["limit"] > 0)
    assert worst > 1.0
