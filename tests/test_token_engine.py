"""TokenEngine over the real kernels (DESIGN.md §13): multi-step greedy
decode parity vs the full forward, ragged (B,)-cache_index decode
equivalence, slot-pool join bit-identity, and mid-stream cascade
escalation carrying the prompt (never the cache)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.cascade import Cascade
from repro.core.gears import Gear
from repro.models import model as M
from repro.serving.token_engine import (SlotEngine, TokenEngine,
                                        TokenRequest, greedy_generate)


def _setup(arch, seed=0):
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    return cfg, params


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "falcon-mamba-7b"])
def test_greedy_decode_matches_forward(arch):
    """prefill + N x decode_step == full forward, position for position,
    along the greedy path (attention KV cache and mamba state cache)."""
    cfg, params = _setup(arch, seed=1)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, size=11).astype(np.int32)
    n_new = 5
    gen, gaps = greedy_generate(params, cfg, prompt, n_new)
    assert gen.shape == (n_new,) and gaps.shape == (n_new,)
    assert np.isfinite(gaps).all() and (gaps >= 0).all()
    # teacher-force the greedy tokens through the full forward: logits at
    # position L-1+k must match the k-th incremental-decode logits
    seq = np.concatenate([prompt, gen])[None, :]
    logits_full, _ = M.forward(params, cfg, {"tokens": jnp.asarray(seq)})
    logits_full = np.asarray(logits_full[0])
    L = prompt.size
    toks = jnp.asarray(prompt[None, :])
    step_logits, cache = M.prefill(params, cfg, {"tokens": toks},
                                   cache_len=L + n_new)
    for k in range(n_new):
        np.testing.assert_allclose(np.asarray(step_logits[0]),
                                   logits_full[L - 1 + k],
                                   atol=5e-2, rtol=0)
        assert int(np.argmax(np.asarray(step_logits[0]))) == int(gen[k])
        step = jnp.asarray([[int(gen[k])]], jnp.int32)
        step_logits, cache = M.decode_step(
            params, cfg, step, cache, jnp.asarray([L + k], jnp.int32))


def test_ragged_decode_matches_per_row():
    """decode_step with a (B,) cache_index equals per-row scalar decodes:
    the ragged batch is bit-invisible to each resident request."""
    cfg, params = _setup("qwen2-0.5b", seed=2)
    rng = np.random.default_rng(1)
    C = 32
    lens = [5, 11, 17]                      # three depths in one batch
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in lens]
    caches, solo = [], []
    nxt = rng.integers(0, cfg.vocab_size, size=3).astype(np.int32)
    for p, t in zip(prompts, nxt):
        _, c1 = M.prefill(params, cfg, {"tokens": jnp.asarray(p[None, :])},
                          cache_len=C)
        caches.append(c1)
        dl, _ = M.decode_step(params, cfg,
                              jnp.asarray([[int(t)]], jnp.int32), c1,
                              jnp.asarray(p.size, jnp.int32))
        solo.append(np.asarray(dl[0]))
    # stack the three b=1 caches into one ragged batch (batch axis 1 of
    # the rep-stacked cache arrays)
    batch_cache = jax.tree.map(
        lambda *xs: jnp.concatenate(xs, axis=1), *caches)
    dl, _ = M.decode_step(params, cfg, jnp.asarray(nxt[:, None]),
                          batch_cache, jnp.asarray(lens, jnp.int32))
    for b in range(3):
        np.testing.assert_array_equal(np.asarray(dl[b]), solo[b])


def test_slot_engine_join_bit_identity():
    """Requests joining a running decode batch get exactly the tokens a
    solo run produces (per-row ragged masks isolate the rows)."""
    cfg, params = _setup("qwen2-0.5b", seed=0)
    rng = np.random.default_rng(0)
    eng = SlotEngine("m", params, cfg, n_slots=4, max_len=40)
    gear = Gear(cascade=Cascade(("m",), ()), min_queue_lens={"m": 1},
                load_fractions={"m": {0: 1.0}})
    te = TokenEngine([eng], gear, min_tokens=2)
    reqs = [TokenRequest(i, rng.integers(0, cfg.vocab_size,
                                         10 + 3 * i).astype(np.int32), 6)
            for i in range(6)]     # 6 requests through 4 slots: real churn
    out = te.serve(reqs)
    for r in reqs:
        solo, sgaps = greedy_generate(params, cfg, r.prompt, r.max_new)
        assert out[r.rid].tokens == solo.tolist()
        assert out[r.rid].resolver == 0
        np.testing.assert_allclose(out[r.rid].gaps, sgaps,
                                   atol=5e-2, rtol=0)
    # slot pool fully recycled
    assert eng.n_active == 0 and sorted(eng.free) == [0, 1, 2, 3]


def test_slot_engine_validation():
    cfg, params = _setup("qwen2-0.5b", seed=0)
    eng = SlotEngine("m", params, cfg, n_slots=1, max_len=16)
    with pytest.raises(ValueError):
        eng.prefill_batch([np.arange(16, dtype=np.int32)])    # no headroom
    (slot,), _, _ = eng.prefill_batch([np.arange(4, dtype=np.int32)])
    with pytest.raises(RuntimeError):
        eng.prefill_batch([np.arange(4, dtype=np.int32)])     # pool full
    eng.release(slot)
    with pytest.raises(ValueError):
        eng.release(slot)                                     # double free


def test_token_engine_midstream_escalation_restarts_from_prompt():
    """An uncertain stream escalates mid-generation; the next model gets
    the PROMPT (never the cache) and its output matches a solo run."""
    cfg, params_a = _setup("qwen2-0.5b", seed=0)
    _, params_b = _setup("qwen2-0.5b", seed=7)
    rng = np.random.default_rng(2)
    stages = [SlotEngine("a", params_a, cfg, n_slots=2, max_len=40),
              SlotEngine("b", params_b, cfg, n_slots=2, max_len=40)]
    # an unreachable threshold forces escalation at the first boundary
    # past min_tokens — every request must hop and resolve at stage 1
    gear = Gear(cascade=Cascade(("a", "b"), (1e9,)),
                min_queue_lens={"a": 1, "b": 1},
                load_fractions={"a": {0: 1.0}, "b": {1: 1.0}})
    te = TokenEngine(stages, gear, min_tokens=2, early_margin=0.5)
    reqs = [TokenRequest(i, rng.integers(0, cfg.vocab_size,
                                         8 + i).astype(np.int32), 6)
            for i in range(3)]
    out = te.serve(reqs)
    for r in reqs:
        assert out[r.rid].resolver == 1
        assert out[r.rid].hops >= 1
        solo, _ = greedy_generate(params_b, cfg, r.prompt, r.max_new)
        assert out[r.rid].tokens == solo.tolist()


def test_token_engine_rejects_mismatched_cascade():
    cfg, params = _setup("qwen2-0.5b", seed=0)
    eng = SlotEngine("x", params, cfg, n_slots=2, max_len=16)
    gear = Gear(cascade=Cascade(("y",), ()), min_queue_lens={"y": 1},
                load_fractions={"y": {0: 1.0}})
    with pytest.raises(ValueError):
        TokenEngine([eng], gear)
