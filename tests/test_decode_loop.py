"""Device-resident decode loop (DESIGN.md §14): fused-loop tokens and gaps
vs the plain greedy reference with decisions vs the token DES, speculative
multi-token scans (K-collapse rule, trace replay, discard), bucketed batched
prefill exactness + gating, the exact-length join on SSM and windowed
stages, the device-side certainty fold vs the host fold, engine-vs-token-DES
decision parity through recorded gap streams, and compile-count
stability."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.cascade import Cascade
from repro.core.certainty import (StreamingCertainty, device_fold_init,
                                  device_fold_update, device_fold_value)
from repro.core.execution import TokenReplayBackend
from repro.core.gears import Gear
from repro.core.lp import Replica
from repro.core.profiles import synthetic_family
from repro.core.scheduling import (CascadeHop, ContinuousBatcher,
                                   SchedulerConfig, SchedulerCore)
from repro.core.simulator import ServingSimulator, SimConfig
from repro.models import model as M
from repro.serving.token_engine import (SlotEngine, TokenEngine,
                                        TokenRequest, greedy_generate)


def _setup(arch, seed=0):
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    return cfg, params


def _requests(cfg, n, rng, base=8, max_new=6):
    return [TokenRequest(i, rng.integers(0, cfg.vocab_size,
                                         base + 3 * i).astype(np.int32),
                         max_new) for i in range(n)]


# The logits leave a bf16 LM head, and |logit| < 1 in these smoke models.
# The engine decodes rows in a batch, greedy_generate one row alone: the
# two round a logit to the same or a neighbouring bf16 value (2**-8 apart
# below 1), so a top-2 gap agrees to two such steps.
GAP_ATOL = 2.0 ** -7


def _gear1():
    return Gear(cascade=Cascade(("m",), ()), min_queue_lens={"m": 1},
                load_fractions={"m": {0: 1.0}})


def _assert_des_decisions(gear, reqs, out, n_slots):
    """Replaying the engine's recorded gap streams through the token DES
    gives each request the same resolver; its hops and its visited stages
    follow from that resolver along the linear cascade."""
    names = list(gear.cascade.models)
    backend = TokenReplayBackend.from_gap_streams(
        names, [out[r.rid].stage_gaps for r in reqs],
        [r.max_new for r in reqs])
    sim = ServingSimulator(
        synthetic_family(names, seed=0),
        [Replica(m, i, 1e-3 * (i + 1)) for i, m in enumerate(names)],
        len(names), SimConfig(max_batch=8))
    res = sim.run_token_trace(
        gear, np.zeros(len(reqs)), [r.prompt.size for r in reqs],
        backend, mode="continuous", n_slots=n_slots, min_tokens=2)
    assert res.completed == len(reqs)
    for r, resolver in zip(reqs, res.resolver):
        o = out[r.rid]
        assert o.resolver == resolver and o.hops == resolver
        assert sorted(o.stage_gaps) == list(range(resolver + 1))


# ---------------------------------------------------------------------------
# Fused loop vs the plain greedy reference, decisions vs the token DES
# ---------------------------------------------------------------------------

def test_fused_matches_reference_bit_identical():
    """The device-resident loop must be invisible: each request gets the
    tokens of a plain prefill + decode_step run, and the DES decides
    alike from its recorded gaps."""
    cfg, params = _setup("qwen2-0.5b", seed=0)
    rng = np.random.default_rng(0)
    reqs = _requests(cfg, 5, rng)
    eng = SlotEngine("m", params, cfg, n_slots=3, max_len=40)
    out = TokenEngine([eng], _gear1(), min_tokens=2).serve(reqs)
    for r in reqs:
        solo, sgaps = greedy_generate(params, cfg, r.prompt, r.max_new)
        assert out[r.rid].tokens == solo.tolist()
        np.testing.assert_allclose(out[r.rid].gaps, sgaps, atol=GAP_ATOL,
                                   rtol=0)
    _assert_des_decisions(_gear1(), reqs, out, n_slots=3)


def test_fused_escalation_matches_reference():
    cfg, params_a = _setup("qwen2-0.5b", seed=0)
    _, params_b = _setup("qwen2-0.5b", seed=7)
    rng = np.random.default_rng(2)
    gear = Gear(cascade=Cascade(("a", "b"), (1e9,)),
                min_queue_lens={"a": 1, "b": 1},
                load_fractions={"a": {0: 1.0}, "b": {1: 1.0}})
    reqs = _requests(cfg, 3, rng, max_new=6)
    stages = [SlotEngine("a", params_a, cfg, n_slots=2, max_len=40),
              SlotEngine("b", params_b, cfg, n_slots=2, max_len=40)]
    out = TokenEngine(stages, gear, min_tokens=2).serve(reqs)
    for r in reqs:
        o = out[r.rid]
        assert o.resolver == 1 and o.hops >= 1
        # the abandoned stage-0 stream is a prefix of stage 0's own run
        _, gaps_a = greedy_generate(params_a, cfg, r.prompt, r.max_new)
        np.testing.assert_allclose(o.stage_gaps[0],
                                   gaps_a[:len(o.stage_gaps[0])],
                                   atol=GAP_ATOL, rtol=0)
        solo, gaps_b = greedy_generate(params_b, cfg, r.prompt, r.max_new)
        assert o.tokens == solo.tolist()
        np.testing.assert_allclose(o.stage_gaps[1], gaps_b,
                                   atol=GAP_ATOL, rtol=0)
    _assert_des_decisions(gear, reqs, out, n_slots=2)


# ---------------------------------------------------------------------------
# Speculative multi-token scans
# ---------------------------------------------------------------------------

def test_spec_k_decisions_match_single_step():
    """K>1 scans change WHEN work executes, never WHAT is decided: same
    tokens, resolver, hops and per-stage gap streams as K=1."""
    cfg, params = _setup("qwen2-0.5b", seed=0)
    rng = np.random.default_rng(1)
    reqs = _requests(cfg, 4, rng, max_new=8)
    outs = {}
    for k in (1, 4):
        eng = SlotEngine("m", params, cfg, n_slots=4, max_len=48)
        te = TokenEngine([eng], _gear1(), min_tokens=2, spec_k=k)
        outs[k] = (te.serve(reqs), te, eng)
    out1, _, _ = outs[1]
    out4, te4, eng4 = outs[4]
    for r in reqs:
        assert out4[r.rid].tokens == out1[r.rid].tokens
        assert out4[r.rid].resolver == out1[r.rid].resolver
        assert out4[r.rid].hops == out1[r.rid].hops
        assert out4[r.rid].stage_gaps.keys() == out1[r.rid].stage_gaps.keys()
    # a terminal-stage stream is never near a boundary, so once everyone
    # is resident the scans actually batch steps: fewer executable calls
    # than decode steps executed
    assert eng4.stats.decode_calls < eng4.stats.decode_steps
    assert te4.spec_discarded == 0       # single stage: nothing discarded


def test_stream_trace_hop_consumes_to_first_decision():
    """The trace replay stops at the first boundary decision; tokens past
    it are speculative and reported as unconsumed."""
    core = SchedulerCore([Replica("a", 0, 1e-3), Replica("b", 1, 2e-3)],
                         SchedulerConfig())
    gear = Gear(cascade=Cascade(("a", "b"), (0.6,)),
                min_queue_lens={"a": 1, "b": 1},
                load_fractions={"a": {0: 1.0}, "b": {1: 1.0}})
    cb = ContinuousBatcher(core, n_slots=4, min_tokens=2, early_margin=0.5)
    cert = StreamingCertainty(mode="min")
    cert.update(0.9)                     # prefill token: confident
    # trace collapses at its 3rd token (min fold -> 0.1 < 0.6 * 0.5)
    used, hop = cb.stream_trace_hop(0, cert, [0.8, 0.7, 0.1, 0.9], 1, 10,
                                    gear)
    assert used == 3 and isinstance(hop, CascadeHop)
    assert cert.count == 4               # unconsumed gap was never folded
    # a confident trace consumes everything and keeps decoding
    cert2 = StreamingCertainty(mode="min")
    cert2.update(0.9)
    used2, hop2 = cb.stream_trace_hop(0, cert2, [0.9, 0.9], 1, 10, gear)
    assert used2 == 2 and hop2 is None


def test_near_boundary_guard_and_validation():
    core = SchedulerCore([Replica("a", 0, 1e-3), Replica("b", 1, 2e-3)],
                         SchedulerConfig())
    gear = Gear(cascade=Cascade(("a", "b"), (0.6,)),
                min_queue_lens={"a": 1, "b": 1},
                load_fractions={"a": {0: 1.0}, "b": {1: 1.0}})
    cb = ContinuousBatcher(core, n_slots=4, min_tokens=2, early_margin=0.5)
    # escalation band is cert < 0.3; slack 1.5 widens nearness to 0.45
    assert cb.near_boundary(0, 0.40, 5, 10, gear, slack=1.5)
    assert not cb.near_boundary(0, 0.50, 5, 10, gear, slack=1.5)
    assert not cb.near_boundary(1, 0.0, 5, 10, gear)   # terminal stage
    cfg, params = _setup("qwen2-0.5b", seed=0)
    eng = SlotEngine("m", params, cfg, n_slots=2, max_len=16)
    for mode in ("reference", "turbo"):    # one loop: only "fused"
        with pytest.raises(ValueError):
            TokenEngine([eng], _gear1(), mode=mode)
    with pytest.raises(ValueError):
        TokenEngine([eng], _gear1(), spec_k=0)
    with pytest.raises(RuntimeError):
        eng.decode_fused()               # nothing resident
    eng.prefill_batch([np.arange(4, dtype=np.int32)])
    with pytest.raises(ValueError):
        eng.decode_fused(k=0)
    with pytest.raises(ValueError):
        eng.decode_fused(k=13)           # 4 + 13 > max_len: scan overrun


# ---------------------------------------------------------------------------
# Device-side certainty fold vs the host fold
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["ewma", "mean", "min"])
def test_device_fold_matches_host_fold(mode):
    rng = np.random.default_rng(0)
    gaps = rng.uniform(0.0, 8.0, size=(12, 3)).astype(np.float32)
    st = device_fold_init(3)
    host = [StreamingCertainty(mode=mode, beta=0.35) for _ in range(3)]
    assert np.all(np.asarray(device_fold_value(st, mode)) == 0.0)
    for t in range(12):
        st = device_fold_update(st, jnp.asarray(gaps[t]), 0.35)
        for b in range(3):
            host[b].update(float(gaps[t, b]))
        np.testing.assert_allclose(
            np.asarray(device_fold_value(st, mode)),
            [h.value for h in host], rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError):
        device_fold_value(st, "median")


# ---------------------------------------------------------------------------
# Bucketed batched prefill: exactness and gating
# ---------------------------------------------------------------------------

def test_prefill_bucketed_matches_per_prompt():
    """Right-padded batched prefill returns each row's true-last-position
    logits — same greedy token as an exact-length batch-1 prefill."""
    cfg, params = _setup("qwen2-0.5b", seed=3)
    rng = np.random.default_rng(4)
    lens = [5, 9, 14]
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    Lb = 16
    arr = np.zeros((4, Lb), np.int32)    # batch-bucket pad row rides along
    tl = np.ones(4, np.int32)
    for i, p in enumerate(prompts):
        arr[i, :p.size] = p
        tl[i] = p.size
    logits_b, _ = M.prefill_bucketed(params, cfg, jnp.asarray(arr),
                                     jnp.asarray(tl), cache_len=32)
    for i, p in enumerate(prompts):
        solo, _ = M.prefill(params, cfg, {"tokens": jnp.asarray(p[None])},
                            cache_len=32)
        np.testing.assert_allclose(np.asarray(logits_b[i]),
                                   np.asarray(solo[0]), atol=1e-4, rtol=0)
        assert int(np.argmax(np.asarray(logits_b[i]))) == \
            int(np.argmax(np.asarray(solo[0])))


def test_bucketed_prefill_gating():
    """Padding is only exact for attention-only decoders: SSM state and
    MoE routing configs must refuse and fall back."""
    mamba = get_smoke_config("falcon-mamba-7b")
    assert not M.bucketed_prefill_supported(mamba)
    qwen = get_smoke_config("qwen2-0.5b")
    assert M.bucketed_prefill_supported(qwen)
    params = M.init_params(mamba, jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        M.prefill_bucketed(params, mamba, jnp.zeros((2, 8), jnp.int32),
                           jnp.asarray([4, 8], jnp.int32), cache_len=16)
    qp = M.init_params(qwen, jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        M.prefill_bucketed(qp, qwen, jnp.zeros((2, 8), jnp.int32),
                           jnp.asarray([4, 8], jnp.int32), cache_len=4)


def test_fused_engine_on_ssm_falls_back_to_exact_prefill():
    """The fused loop still serves SSM cascades bit-identically — joins
    just use exact-length prefills (no padded batching)."""
    cfg, params = _setup("falcon-mamba-7b", seed=1)
    rng = np.random.default_rng(5)
    eng = SlotEngine("m", params, cfg, n_slots=2, max_len=32)
    te = TokenEngine([eng], _gear1(), min_tokens=2)
    reqs = _requests(cfg, 3, rng, base=6, max_new=4)
    out = te.serve(reqs)
    for r in reqs:
        solo, _ = greedy_generate(params, cfg, r.prompt, r.max_new)
        assert out[r.rid].tokens == solo.tolist()
    # every prefill went through the exact-length batch-1 path
    assert all(b == 1 for b, _ in eng.stats.prefill_shapes)


def test_fused_engine_on_window_falls_back_to_exact_prefill():
    """A windowed stage whose length bucket reaches the window joins
    through the exact-length prefill (pads would alias live ring slots)
    and still serves each request the plain greedy tokens."""
    cfg, params = _setup("h2o-danube-1.8b", seed=2)
    assert cfg.sliding_window == 64
    rng = np.random.default_rng(9)
    eng = SlotEngine("m", params, cfg, n_slots=2, max_len=128)
    te = TokenEngine([eng], _gear1(), min_tokens=2)
    # two at a time through two slots: each pair's longest prompt has a
    # length bucket of 64 or 128, so the 20-token one joins exactly too
    lens = (40, 63, 70, 20)
    reqs = [TokenRequest(i, rng.integers(0, cfg.vocab_size,
                                         n).astype(np.int32), 6)
            for i, n in enumerate(lens)]
    out = te.serve(reqs)
    for r in reqs:
        solo, _ = greedy_generate(params, cfg, r.prompt, r.max_new)
        assert out[r.rid].tokens == solo.tolist()
    assert eng.stats.prefill_shapes == {(1, n) for n in lens}
    cc = eng.compile_counts()
    assert cc["exact_prefill"] == len(lens) and cc["bucketed_prefill"] == 0


# ---------------------------------------------------------------------------
# Engine vs token DES: decision parity through recorded gap streams
# ---------------------------------------------------------------------------

def test_engine_vs_token_des_decision_parity():
    """Replaying the engine's recorded gap streams through the token DES
    reproduces its resolver/hop decisions exactly — the engine and the
    DES share one decision layer (DESIGN.md §13/§14)."""
    cfg, params_a = _setup("qwen2-0.5b", seed=0)
    _, params_b = _setup("qwen2-0.5b", seed=7)
    rng = np.random.default_rng(6)
    reqs = _requests(cfg, 5, rng, max_new=6)
    # pick a threshold that splits the population: median of the solo
    # end-of-stream certainty folds
    finals = []
    for r in reqs:
        _, gaps = greedy_generate(params_a, cfg, r.prompt, r.max_new)
        c = StreamingCertainty()
        for g in gaps:
            c.update(float(g))
        finals.append(c.value)
    thr = float(np.median(finals))
    gear = Gear(cascade=Cascade(("a", "b"), (thr,)),
                min_queue_lens={"a": 1, "b": 1},
                load_fractions={"a": {0: 1.0}, "b": {1: 1.0}},
                decode_slots={"a": 3, "b": 3})
    for spec_k in (1, 3):
        stages = [SlotEngine("a", params_a, cfg, n_slots=3, max_len=40),
                  SlotEngine("b", params_b, cfg, n_slots=3, max_len=40)]
        te = TokenEngine(stages, gear, min_tokens=2, spec_k=spec_k)
        out = te.serve(reqs)
        resolvers = [out[r.rid].resolver for r in reqs]
        assert 0 in resolvers and 1 in resolvers    # threshold splits
        backend = TokenReplayBackend.from_gap_streams(
            ["a", "b"], [out[r.rid].stage_gaps for r in reqs],
            [r.max_new for r in reqs])
        sim = ServingSimulator(
            synthetic_family(["a", "b"], seed=0),
            [Replica("a", 0, 1e-3), Replica("b", 1, 2e-3)], 2,
            SimConfig(max_batch=8))
        res = sim.run_token_trace(
            gear, np.zeros(len(reqs)), [r.prompt.size for r in reqs],
            backend, mode="continuous", n_slots=3, min_tokens=2)
        assert res.completed == len(reqs)
        np.testing.assert_array_equal(res.resolver, resolvers)
        np.testing.assert_array_equal(
            res.tokens_out, [len(out[r.rid].tokens) for r in reqs])
        # the busy-time breakdown covers both phases and adds up
        assert set(res.per_model_prefill_time) <= {"a", "b"}
        total = sum(res.per_model_prefill_time.values()) + \
            sum(res.per_model_decode_time.values())
        assert total == pytest.approx(float(res.device_busy.sum()))


def test_from_gap_streams_validation():
    with pytest.raises(ValueError):
        TokenReplayBackend.from_gap_streams(["a"], [], [])
    with pytest.raises(ValueError):
        TokenReplayBackend.from_gap_streams(["a"], [{0: [1.0]}], [1, 2])


# ---------------------------------------------------------------------------
# Compile stability
# ---------------------------------------------------------------------------

def test_compile_counts_bounded_by_bucket_grid():
    """The fused engine's executable count is bounded by the bucket grid
    regardless of the prompt-length distribution; the exact-length
    fallback (an SSM stage) compiles one prefill per DISTINCT length."""
    cfg, params = _setup("qwen2-0.5b", seed=0)
    rng = np.random.default_rng(7)
    lens = [5, 6, 7, 9, 11, 13, 17, 19]          # 8 distinct lengths
    reqs = [TokenRequest(i, rng.integers(0, cfg.vocab_size,
                                         n).astype(np.int32), 4)
            for i, n in enumerate(lens)]
    eng = SlotEngine("m", params, cfg, n_slots=4, max_len=40)
    te = TokenEngine([eng], _gear1(), min_tokens=2)
    te.serve(reqs)
    cc = eng.compile_counts()
    grid = len(eng.len_buckets) * len(eng.batch_buckets)
    assert cc["bucketed_prefill"] == len(eng.stats.prefill_shapes) <= grid
    assert cc["bucketed_prefill"] < len(set(lens))   # beats per-length
    assert cc["fused_decode"] == 1                   # K=1 only
    assert cc["exact_prefill"] == 0
    # the exact-length fallback's compile count tracks the distribution
    mcfg, mparams = _setup("falcon-mamba-7b", seed=0)
    ssm = SlotEngine("m", mparams, mcfg, n_slots=4, max_len=40)
    TokenEngine([ssm], _gear1(), min_tokens=2).serve(reqs)
    sc = ssm.compile_counts()
    assert sc["exact_prefill"] == len(set(lens))
    assert sc["bucketed_prefill"] == 0


def test_fused_step_transfer_is_o_b():
    """Per decode step the fused loop ships O(B) scalars where a host
    loop would ship the (B, V) f32 logits — the transfer claim, counted."""
    cfg, params = _setup("qwen2-0.5b", seed=0)
    rng = np.random.default_rng(8)
    reqs = _requests(cfg, 3, rng, max_new=5)
    eng = SlotEngine("m", params, cfg, n_slots=3, max_len=40)
    TokenEngine([eng], _gear1(), min_tokens=2).serve(reqs)
    fused = 12 * eng.n_slots                 # (B,) token + gap + cert
    logits = 4 * eng.n_slots * cfg.vocab_size
    # every byte to the host: the decode steps' traces plus one
    # (token, gap) pair per prefilled prompt
    assert eng.stats.decode_steps > 0
    assert eng.stats.bytes_to_host == \
        eng.stats.decode_steps * fused + 8 * eng.stats.prefill_prompts
    assert logits / fused == pytest.approx(cfg.vocab_size / 3.0)
