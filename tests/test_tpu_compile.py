"""Compiles for a described TPU v5e (nothing runs): the served top-2-gap
kernel at the batch and vocab sizes the engines use, and the fused decode
step at qwen2-0.5b and h2o-danube-1.8b widths and the bucketed prefill at
qwen2-0.5b widths, with the kernel inside.

Interpret mode, which the kernel tests use, accepts block shapes that the
chip's compiler refuses; these compiles are what catches that here."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.certainty import device_fold_init
from repro.kernels import top2gap
from repro.models import model as M


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def kernel_backend(monkeypatch):
    """``argmax_gap`` takes the Pallas branch only where JAX's default
    backend is the TPU; the compiles below are for a TPU from a CPU host."""
    monkeypatch.setattr(top2gap.jax, "default_backend", lambda: "tpu")


def _spec(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("b,v", [(16, 151936), (32, 32000), (1, 50304),
                                 (3, 32000), (64, 151936)])
def test_top2gap_compiles(one_chip, b, v):
    x = jax.ShapeDtypeStruct((b, v), jnp.float32, sharding=one_chip)
    hlo = jax.jit(top2gap.top2gap_pallas).lower(x).compile().as_text()
    assert "tpu_custom_call" in hlo


def _cut(name):
    # published widths, depth cut to two layers: compile time, not shapes
    return get_config(name).scaled(num_layers=2)


@pytest.fixture(scope="module")
def qwen2_cut():
    return _cut("qwen2-0.5b")


@pytest.mark.parametrize("name", ["qwen2-0.5b", "h2o-danube-1.8b"])
def test_decode_fused_step_compiles_with_kernel(one_chip, kernel_backend,
                                                name):
    cfg, b, c_len = _cut(name), 16, 2048
    params = _spec(M.init_params(cfg, spec_only=True), one_chip)
    cache = _spec(M.init_cache(cfg, b, c_len, spec_only=True), one_chip)
    fold = _spec(jax.eval_shape(lambda: device_fold_init(b)), one_chip)
    rows = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
    active = jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=one_chip)
    step = jax.jit(lambda p, t, c, pos, a, st: M.decode_fused_steps(
        p, cfg, t, c, pos, a, st, k=1), donate_argnums=(1, 2, 3, 5))
    hlo = step.lower(params, rows, cache, rows, active, fold) \
        .compile().as_text()
    assert "tpu_custom_call" in hlo
    # one device attends grouped: the cache is never broadcast to its
    # query heads (B, C, KV, G, hd)
    kv = cfg.num_kv_heads
    group = f"[{b},{c_len},{kv},{cfg.num_heads // kv},{cfg.head_dim}]"
    assert group not in hlo


def test_bucketed_prefill_compiles_with_kernel(one_chip, qwen2_cut,
                                               kernel_backend):
    cfg, b, lb = qwen2_cut, 16, 512
    params = _spec(M.init_params(cfg, spec_only=True), one_chip)
    tokens = jax.ShapeDtypeStruct((b, lb), jnp.int32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)

    def prefill(p, t, n):
        logits, cache = M.prefill_bucketed(p, cfg, t, n, cache_len=2048)
        return top2gap.argmax_gap(logits), cache

    hlo = jax.jit(prefill).lower(params, tokens, lens).compile().as_text()
    assert "tpu_custom_call" in hlo
