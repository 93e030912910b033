"""The ExecutionBackend layer (core/execution.py): the three backends, the
unified profile entry point, engine bucketing/padding correctness, and the
constructor validation that replaced bare asserts."""
import numpy as np
import pytest

from repro.core.cascade import Cascade
from repro.core.execution import (BatchExecution, CostModelBackend,
                                  EngineBackend, ReplayBackend,
                                  profile_backend, resolve_estimator)
from repro.core.gears import GearPlan, SLO
from repro.core.lp import Replica
from repro.core.profiles import (ModelProfile, ValidationRecord,
                                 synthetic_family)
from repro.core.simulator import ServingSimulator, SimConfig, make_gear


# ---------------------------------------------------------------------------
# ReplayBackend
# ---------------------------------------------------------------------------

def test_replay_backend_replays_validation(bert_like_profiles):
    b = ReplayBackend(bert_like_profiles)
    rec = bert_like_profiles["tiny"].validation
    n = len(rec.certs)
    sids = [0, 3, n + 3, 2 * n]      # wraps around the validation set
    ex = b.execute("tiny", sids)
    assert list(ex.certs) == [rec.certs[s % n] for s in sids]
    assert list(ex.correct) == [bool(rec.correct[s % n]) for s in sids]
    assert ex.elapsed is None        # virtual physics: no wall time spent
    # runtimes come from profile interpolation
    assert b.batch_runtime("tiny", 4) == \
        bert_like_profiles["tiny"].runtime(4)


def test_simulator_identical_through_explicit_replay_backend(
        bert_like_profiles):
    """Default backend vs explicitly passed ReplayBackend: the refactor
    contract is that the driver never special-cases the source, so both
    must produce the bit-identical SimResult."""
    profiles = bert_like_profiles
    reps = [Replica(m, d, profiles[m].runtime_per_sample(1.0))
            for d in range(2) for m in ("tiny", "base")]
    g = make_gear(Cascade(("tiny", "base"), (0.35,)), reps, {"tiny": 2})
    plan = GearPlan(qps_max=400.0, gears=[g], replicas=reps, num_devices=2,
                    slo=SLO(kind="latency", latency_p95=1.0))
    trace = np.concatenate([np.full(3, 60.0), np.full(3, 300.0)])
    r1 = ServingSimulator(profiles, reps, 2, SimConfig(max_batch=128)) \
        .run_trace(plan, trace)
    r2 = ServingSimulator(profiles, reps, 2, SimConfig(max_batch=128),
                          backend=ReplayBackend(profiles)) \
        .run_trace(plan, trace)
    assert r1.completed == r2.completed
    assert np.array_equal(r1.latencies, r2.latencies)
    assert np.array_equal(r1.correct, r2.correct)
    assert np.array_equal(r1.resolver, r2.resolver)


def test_replay_backend_profile_is_the_stored_artifact(bert_like_profiles):
    b = ReplayBackend(bert_like_profiles)
    assert profile_backend(b, "tiny") is bert_like_profiles["tiny"]
    # resampling onto a new grid uses the same runtime interpolation
    p = profile_backend(b, "tiny", batch_sizes=(3, 5))
    assert p.batch_runtimes[0] == bert_like_profiles["tiny"].runtime(3)
    # the set form covers every model the backend serves
    ps = profile_backend(b)
    assert set(ps) == set(bert_like_profiles)


# ---------------------------------------------------------------------------
# EngineBackend
# ---------------------------------------------------------------------------

class _RowEngine:
    """Fake engine whose scores encode the input rows, so padding leaks and
    row misalignment are detectable: scores[i] = (tokens[i,0], -1)."""

    def __init__(self):
        self.batch_sizes = []

    def infer(self, tokens):
        tokens = np.asarray(tokens)
        self.batch_sizes.append(tokens.shape[0])
        out = np.full((tokens.shape[0], 2), -1.0)
        out[:, 0] = tokens[:, 0]
        return out


def test_engine_backend_matches_engine_plus_estimator():
    eng = _RowEngine()
    b = EngineBackend({"m": eng}, estimator=lambda s: s[:, 0] - s[:, 1])
    toks = [np.array([7, 0]), np.array([2, 0])]
    ex = b.execute("m", [0, 1], tokens=toks)
    assert list(ex.preds) == [0, 0]
    assert list(ex.certs) == [8.0, 3.0]     # (7 - -1), (2 - -1)
    assert ex.correct is None               # no labels attached
    assert ex.elapsed is not None and ex.elapsed >= 0.0


def test_engine_backend_estimator_sees_pow2_rows():
    """The estimator runs on the batch padded to a power-of-two row count,
    so a jitted estimator compiles once per size class, not once per batch
    size; the real rows' certainties are unchanged."""
    seen = []

    def est(s):
        seen.append(s.shape[0])
        return s[:, 0] - s[:, 1]

    b = EngineBackend({"m": _RowEngine()}, estimator=est)
    for n in (1, 3, 5, 8):
        ex = b.execute("m", list(range(n)),
                       tokens=[np.array([i, 0]) for i in range(n)])
        assert list(ex.certs) == [i + 1.0 for i in range(n)]
        assert len(ex.preds) == n
    assert seen == [1, 4, 8, 8]


def test_engine_backend_token_and_label_pools():
    """With sid-indexed pools the backend executes from sample ids alone
    (what lets the DES drive real models) and reports correctness."""
    pool = np.arange(6, dtype=np.int64).reshape(3, 2) * 10
    labels = np.array([0, 1, 0])
    b = EngineBackend({"m": _RowEngine()}, estimator=lambda s: s[:, 0],
                      tokens=pool, labels=labels)
    ex = b.execute("m", [1, 3])             # 3 wraps to pool row 0
    assert list(ex.certs) == [20.0, 0.0]
    # preds are always 0 (scores[:,0] >= scores[:,1]) -> correct vs labels
    assert ex.correct == [False, True]
    # caller-supplied tokens are NOT the pool's: pairing their predictions
    # with pool labels would be noise, so correctness must be unknown
    ex2 = b.execute("m", [1, 3], tokens=[np.array([5, 0]),
                                         np.array([6, 0])])
    assert ex2.correct is None
    with pytest.raises(RuntimeError):
        EngineBackend({"m": _RowEngine()}).execute("m", [0])  # no pool


def test_simulator_unknown_correctness_reads_nan(bert_like_profiles):
    """Real models in the DES without a label pool: latency metrics are
    valid, but accuracy must read UNKNOWN (nan), never silently 0.0."""
    import math
    profiles = bert_like_profiles
    reps = [Replica("tiny", 0, profiles["tiny"].runtime_per_sample(1.0))]
    g = make_gear(Cascade(("tiny",), ()), reps)
    plan = GearPlan(qps_max=200.0, gears=[g], replicas=reps, num_devices=1,
                    slo=SLO(kind="latency", latency_p95=1.0))
    pool = np.zeros((8, 2), np.int64)
    b = EngineBackend({"tiny": _RowEngine()}, estimator=lambda s: s[:, 0],
                      tokens=pool, profiles=profiles)   # tokens, NO labels
    sim = ServingSimulator(profiles, reps, 1, backend=b)
    r = sim.run_trace(plan, np.full(2, 30.0))
    assert r.completed == r.offered > 0
    assert not r.correctness_known
    assert math.isnan(r.accuracy)
    # the default replay physics still knows correctness
    r2 = ServingSimulator(profiles, reps, 1).run_trace(plan,
                                                       np.full(2, 30.0))
    assert r2.correctness_known and not math.isnan(r2.accuracy)


def test_engine_backend_requires_profiles_for_virtual_time():
    b = EngineBackend({"m": _RowEngine()})
    with pytest.raises(RuntimeError):
        b.batch_runtime("m", 4)
    prof = ModelProfile(name="m", mem_bytes=1.0,
                        batch_sizes=np.array([1.0, 8.0]),
                        batch_runtimes=np.array([1e-3, 4e-3]),
                        validation=ValidationRecord(
                            certs=np.zeros(4), correct=np.ones(4, bool)))
    b2 = EngineBackend({"m": _RowEngine()}, profiles={"m": prof})
    assert b2.batch_runtime("m", 8) == pytest.approx(4e-3)


# ---------------------------------------------------------------------------
# InferenceEngine bucketing / padding / profiling (satellite coverage)
# ---------------------------------------------------------------------------

def test_engine_padding_does_not_leak_into_scores():
    """Padded rows must neither appear in the returned scores nor displace
    the real rows: row i of the output must correspond to input row i."""
    from repro.serving.engine import InferenceEngine
    import jax.numpy as jnp
    seen = []

    def apply_fn(params, tokens):
        seen.append(int(tokens.shape[0]))
        out = jnp.stack([tokens[:, 0].astype(jnp.float32),
                         jnp.full((tokens.shape[0],), -1.0)], axis=-1)
        return out

    eng = InferenceEngine("x", apply_fn, {}, buckets=(1, 2, 4, 8))
    toks = np.arange(3, dtype=np.int32)[:, None] + 5   # rows 5, 6, 7
    out = eng.infer(np.repeat(toks, 4, axis=1))
    assert seen[-1] == 4                   # padded up to the 4-bucket
    assert out.shape == (3, 2)             # pad rows sliced away
    assert out[:, 0].tolist() == [5.0, 6.0, 7.0]   # alignment preserved


def test_engine_oversized_batch_split_preserves_rows():
    from repro.serving.engine import InferenceEngine
    import jax.numpy as jnp

    def apply_fn(params, tokens):
        return jnp.stack([tokens[:, 0].astype(jnp.float32),
                          jnp.zeros((tokens.shape[0],))], axis=-1)

    eng = InferenceEngine("x", apply_fn, {}, buckets=(1, 2, 4, 8))
    n = 13                                  # 8 + 5(->8 bucket)
    toks = np.arange(n, dtype=np.int32)[:, None].repeat(2, axis=1)
    out = eng.infer(toks)
    assert out.shape == (n, 2)
    assert out[:, 0].tolist() == list(range(n))


def test_profile_engine_positive_sorted_runtimes():
    from repro.serving.engine import InferenceEngine, profile_engine
    import jax.numpy as jnp

    def apply_fn(params, tokens):
        return jnp.zeros((tokens.shape[0], 2))

    eng = InferenceEngine("x", apply_fn, {}, buckets=(1, 2, 4, 8))
    p = profile_engine(eng, seq_len=4, batch_sizes=(4, 1, 8), repeats=2)
    assert np.all(p.batch_runtimes > 0.0)
    # profile normalises onto an ascending batch-size grid
    assert p.batch_sizes.tolist() == [1.0, 4.0, 8.0]
    assert p.name == "x"


# ---------------------------------------------------------------------------
# CostModelBackend
# ---------------------------------------------------------------------------

def test_cost_model_backend_matches_analytic_profile():
    from repro.configs import get_config
    from repro.profiling.cost_model import profile_from_cost_model
    arch = "qwen2-0.5b"
    b = CostModelBackend({arch: arch}, context=512,
                         batch_sizes=(1, 4, 16))
    direct = profile_from_cost_model(get_config(arch), context=512,
                                     kind="decode", batch_sizes=(1, 4, 16))
    p = profile_backend(b, arch)
    assert np.allclose(p.batch_runtimes, direct.batch_runtimes)
    assert p.devices_per_replica == direct.devices_per_replica
    assert b.batch_runtime(arch, 4) == pytest.approx(direct.runtime(4))
    # and it replays like any other backend (synthetic default validation)
    ex = b.execute(arch, [0, 1])
    assert len(ex.certs) == 2


def test_cost_model_backend_carries_validation_structure():
    synth = synthetic_family(["a"], seed=7, n_val=64)
    b = CostModelBackend({"a": "qwen2-0.5b"},
                         validation={"a": synth["a"].validation},
                         batch_sizes=(1, 4))
    assert b.validation_record("a") is synth["a"].validation
    ex = b.execute("a", list(range(5)))
    assert list(ex.certs) == synth["a"].validation.certs[:5].tolist()


# ---------------------------------------------------------------------------
# resolve_estimator (single home of the estimator lookup)
# ---------------------------------------------------------------------------

def test_resolve_estimator():
    fn = resolve_estimator("top2_gap")
    scores = np.array([[3.0, 1.0, 0.5]])
    assert float(np.asarray(fn(scores))[0]) == pytest.approx(2.0)
    marker = lambda s: s                       # noqa: E731
    assert resolve_estimator(marker) is marker  # callables pass through
    with pytest.raises(ValueError):
        resolve_estimator("nope")


# ---------------------------------------------------------------------------
# Constructor validation (explicit ValueErrors, not bare asserts)
# ---------------------------------------------------------------------------

def test_cascade_validation_raises_value_error():
    with pytest.raises(ValueError):
        Cascade(("a", "b"), ())                # missing threshold
    with pytest.raises(ValueError):
        Cascade((), ())                        # no models


def test_validation_record_raises_value_error():
    with pytest.raises(ValueError):
        ValidationRecord(certs=np.zeros(3), correct=np.ones(2, bool))
    with pytest.raises(ValueError):
        ValidationRecord(certs=np.zeros(0), correct=np.zeros(0, bool))
    with pytest.raises(ValueError):
        ValidationRecord(certs=np.zeros(3), correct=np.ones(3, bool),
                         preds=np.zeros(2, np.int64))


def test_model_profile_raises_value_error():
    rec = ValidationRecord(certs=np.zeros(2), correct=np.ones(2, bool))
    with pytest.raises(ValueError):
        ModelProfile(name="m", mem_bytes=1.0,
                     batch_sizes=np.array([1.0, 2.0]),
                     batch_runtimes=np.array([1e-3]), validation=rec)
    with pytest.raises(ValueError):
        ModelProfile(name="m", mem_bytes=1.0, batch_sizes=np.array([]),
                     batch_runtimes=np.array([]), validation=rec)
    with pytest.raises(ValueError):
        ModelProfile(name="m", mem_bytes=1.0, batch_sizes=np.array([0.0]),
                     batch_runtimes=np.array([1e-3]), validation=rec)
    with pytest.raises(ValueError):
        ModelProfile(name="m", mem_bytes=1.0, batch_sizes=np.array([1.0]),
                     batch_runtimes=np.array([-1e-3]), validation=rec)
    with pytest.raises(ValueError):
        ModelProfile(name="m", mem_bytes=1.0, batch_sizes=np.array([1.0]),
                     batch_runtimes=np.array([np.inf]), validation=rec)


# ---------------------------------------------------------------------------
# Cross-driver: the wall-clock server on replayed physics, and the
# virtual-time server defaulting to its backend's runtime model
# ---------------------------------------------------------------------------

def test_threaded_server_serves_replay_backend(bert_like_profiles):
    """ReplayBackend behind the REAL threaded machinery: compute-free
    serving (the high-QPS stress configuration)."""
    import time as _time
    from repro.serving.runtime import CascadeServer, Request
    profiles = bert_like_profiles
    reps = [Replica("tiny", 0, profiles["tiny"].runtime_per_sample(1.0))]
    g = make_gear(Cascade(("tiny",), ()), reps)
    plan = GearPlan(qps_max=500.0, gears=[g], replicas=reps, num_devices=1,
                    slo=SLO(kind="latency", latency_p95=1.0))
    server = CascadeServer(plan, backend=ReplayBackend(profiles))
    server.start()
    for i in range(32):
        server.submit(Request(rid=i, tokens=np.zeros(1, np.int32)))
    deadline = _time.monotonic() + 5.0
    while len(server.completed) < 32 and _time.monotonic() < deadline:
        _time.sleep(0.01)
    server.stop()
    assert len(server.completed) == 32
    rec = profiles["tiny"].validation
    done = sorted(server.completed, key=lambda r: r.rid)
    n = len(rec.certs)
    assert [r.cert for r in done] == \
        [rec.certs[r.rid % n] for r in done]


def test_run_virtual_defaults_to_backend_runtime(bert_like_profiles):
    """run_virtual without an explicit batch_runtime uses the backend's
    own runtime model — same results as passing the profile lookup."""
    from repro.serving.runtime import CascadeServer, Request
    profiles = bert_like_profiles
    reps = [Replica(m, d, profiles[m].runtime_per_sample(1.0))
            for d in range(2) for m in ("tiny", "base")]
    g = make_gear(Cascade(("tiny", "base"), (0.35,)), reps, {"tiny": 2})
    plan = GearPlan(qps_max=400.0, gears=[g], replicas=reps, num_devices=2,
                    slo=SLO(kind="latency", latency_p95=1.0))
    trace = np.full(3, 80.0)

    def run(**kw):
        server = CascadeServer(plan, backend=ReplayBackend(profiles))
        n = int(trace.sum()) + 4
        reqs = [Request(rid=i, tokens=np.zeros(1, np.int32))
                for i in range(n)]
        return server.run_virtual(reqs, trace, **kw)

    implicit = run()
    explicit = run(batch_runtime=lambda m, b: profiles[m].runtime(b))
    assert len(implicit) == len(explicit) > 0
    assert [r.t_done for r in implicit] == [r.t_done for r in explicit]


class _FailingReplay(ReplayBackend):
    """Replay physics whose third batch raises, as a crashing model would."""

    def __init__(self, profiles):
        super().__init__(profiles)
        self.calls = 0

    def execute(self, model, sids, tokens=None):
        self.calls += 1
        if self.calls == 3:
            raise FloatingPointError("batch 3 blew up")
        return super().execute(model, sids, tokens)


def test_threaded_server_raises_a_consumer_failure(bert_like_profiles):
    """A consumer thread that raises fails ``run_trace`` with its exception
    (instead of dying silently and returning a short result), and cuts the
    trace and the drain short."""
    import time as _time
    from repro.serving.runtime import CascadeServer, Request
    profiles = bert_like_profiles
    reps = [Replica("tiny", 0, profiles["tiny"].runtime_per_sample(1.0))]
    g = make_gear(Cascade(("tiny",), ()), reps)
    plan = GearPlan(qps_max=500.0, gears=[g], replicas=reps, num_devices=1,
                    slo=SLO(kind="latency", latency_p95=1.0))
    backend = _FailingReplay(profiles)
    server = CascadeServer(plan, backend=backend)
    trace = np.full(4, 50.0)
    reqs = [Request(rid=i, tokens=np.zeros(1, np.int32))
            for i in range(int(trace.sum()) + 4)]
    t0 = _time.monotonic()
    with pytest.raises(FloatingPointError, match="batch 3"):
        server.run_trace(reqs, trace, drain=30.0)
    assert _time.monotonic() - t0 < 10.0
    assert not any(t.is_alive() for t in server._threads)
    server.stop()                    # the failure is reported once


def test_threaded_server_runs_batches_on_bound_devices(bert_like_profiles):
    """``devices=`` binds plan device d to a JAX device: each consumer runs
    its batches there, as the devices of the engines' outputs show."""
    import time as _time
    import jax
    import jax.numpy as jnp
    from repro.serving.engine import InferenceEngine
    from repro.serving.runtime import CascadeServer, Request
    profiles = bert_like_profiles
    reps = [Replica("tiny", d, profiles["tiny"].runtime_per_sample(1.0))
            for d in range(2)]
    g = make_gear(Cascade(("tiny",), ()), reps)
    plan = GearPlan(qps_max=500.0, gears=[g], replicas=reps, num_devices=2,
                    slo=SLO(kind="latency", latency_p95=1.0))
    eng = InferenceEngine(
        "tiny", lambda p, t: jnp.stack([t[:, 0] * p, -t[:, 0] * p], -1)
        .astype(jnp.float32), jnp.float32(1.0), buckets=(1, 2, 4, 8))
    dev = jax.devices()[0]
    with pytest.raises(ValueError):
        CascadeServer(plan, engines={"tiny": eng}, devices=[dev])
    server = CascadeServer(plan, engines={"tiny": eng}, devices=[dev, dev])
    server.start()
    for i in range(16):
        server.submit(Request(rid=i, tokens=np.full(4, i + 1, np.int32)))
    deadline = _time.monotonic() + 10.0
    while len(server.completed) < 16 and _time.monotonic() < deadline:
        _time.sleep(0.01)
    server.stop()
    assert len(server.completed) == 16
    assert all(r.pred == 0 for r in server.completed)
    assert set(eng.batches_by_device) == {dev}
    assert eng.params_on(dev).devices() == {dev}
