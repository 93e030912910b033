"""Multi-device integration (subprocess: XLA device-count flag must be set
before jax initialises, which the main pytest process has already done)."""
import os
import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec
from repro.configs import get_smoke_config
from repro.models import model as M
from repro.launch.mesh import make_mesh, context_for_mesh
from repro.distributed.context import use_context
from repro.distributed import sharding as sh
from repro.training import (AdamWConfig, make_train_step, TrainStepConfig,
                            init_opt_state, opt_state_pspecs, SyntheticDataset)

# 1) EP MoE parity: sharded loss == local loss (within capacity/bf16 noise)
cfg = get_smoke_config("qwen2-moe-a2.7b")
mesh = make_mesh((4, 2), ("data", "model"))
ctx = context_for_mesh(mesh)
params = M.init_params(cfg, jax.random.PRNGKey(0))
batch = {"tokens": jnp.zeros((8, 16), jnp.int32) + 3,
         "labels": jnp.ones((8, 16), jnp.int32)}
loss_ref, _ = M.train_loss(params, cfg, batch, remat=False)
pspecs = sh.param_shardings(params, ctx, mode="train")
params_sh = jax.device_put(params, pspecs)
with use_context(ctx):
    loss_sh = jax.jit(lambda p, b: M.train_loss(p, cfg, b, remat=False)[0])(
        params_sh, batch)
delta = abs(float(loss_ref) - float(loss_sh))
assert delta < 2e-2, f"EP parity delta {delta}"
print("EP_PARITY_OK", delta)

# 2) multi-pod train step with int8 pod-compressed grads + ZeRO-1
cfg2 = get_smoke_config("olmo-1b")
mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
ctx3 = context_for_mesh(mesh3)
p2 = M.init_params(cfg2, jax.random.PRNGKey(0))
pspecs2 = sh.param_pspecs(p2, ctx3, mode="train")
p2 = jax.device_put(p2, jax.tree.map(
    lambda s: NamedSharding(mesh3, s), pspecs2,
    is_leaf=lambda s: isinstance(s, PartitionSpec)))
opt2 = init_opt_state(p2)
ospecs = opt_state_pspecs(pspecs2, zero1_axis="pod")
opt2 = jax.device_put(opt2, jax.tree.map(
    lambda s: NamedSharding(mesh3, s), ospecs,
    is_leaf=lambda s: isinstance(s, PartitionSpec)))
ds = SyntheticDataset(cfg2, batch=8, seq_len=32, seed=0)
step = make_train_step(cfg2, AdamWConfig(learning_rate=1e-3, warmup_steps=2,
                                         decay_steps=50),
                       TrainStepConfig(remat=True, compress_pod_grads=True))
losses = []
with use_context(ctx3):
    jitted = jax.jit(step)
    for _ in range(6):
        b = {k: jnp.asarray(v) for k, v in ds.next_batch().items()}
        p2, opt2, m = jitted(p2, opt2, b)
        losses.append(float(m["loss"]))
assert losses[-1] < losses[0], losses
print("MULTIPOD_TRAIN_OK", losses[0], "->", losses[-1])

# 3) ZeRO-1: moments really are sharded over the pod axis
mspec = jax.tree.leaves(opt2["m"])[1].sharding.spec
assert any("pod" == a or (isinstance(a, tuple) and "pod" in a)
           for a in mspec if a is not None), mspec
print("ZERO1_SHARDING_OK")

# 4) sharded flash-decoding == dense decode (EXPERIMENTS.md Perf H2)
cfg4 = get_smoke_config("qwen3-32b")
p4 = M.init_params(cfg4, jax.random.PRNGKey(1), dtype=jnp.float32)
toks = jax.random.randint(jax.random.PRNGKey(2), (4, 17), 0, cfg4.vocab_size)
_, cache_ref = M.prefill(p4, cfg4, {"tokens": toks[:, :16]}, cache_len=17)
ref, _ = M.decode_step(p4, cfg4, toks[:, 16:17], cache_ref,
                       jnp.asarray(16, jnp.int32))
mesh4 = make_mesh((2, 4), ("data", "model"))
ctx4 = context_for_mesh(mesh4, flash_decode=True)
_, cache20 = M.prefill(p4, cfg4, {"tokens": toks[:, :16]}, cache_len=20)
with use_context(ctx4):
    out, _ = jax.jit(lambda p, c, t, i: M.decode_step(p, cfg4, t, c, i))(
        p4, cache20, toks[:, 16:17], jnp.asarray(16, jnp.int32))
err = float(jnp.max(jnp.abs(out - ref)))
assert err < 1e-4, f"flash decode err {err}"
print("FLASH_DECODE_OK", err)

# 5) sequence-parallel attention parity (Perf H3; 14 heads, 4-way model)
cfg5 = get_smoke_config("qwen2-0.5b")  # 4 smoke heads; force non-tiling
import dataclasses
cfg5 = dataclasses.replace(cfg5, num_heads=6, num_kv_heads=2, head_dim=32,
                           d_model=192)
p5 = M.init_params(cfg5, jax.random.PRNGKey(3), dtype=jnp.float32)
batch5 = {"tokens": jax.random.randint(jax.random.PRNGKey(4), (4, 24), 0,
                                       cfg5.vocab_size)}
ref5, _ = M.forward(p5, cfg5, batch5)
with use_context(context_for_mesh(mesh4)):
    out5, _ = jax.jit(lambda p, b: M.forward(p, cfg5, b))(p5, batch5)
err5 = float(jnp.max(jnp.abs(out5 - ref5)))
assert err5 < 1e-3, f"seq-parallel err {err5}"
print("SEQ_PARALLEL_OK", err5)

# 6) dense decode with a mesh set keeps the repeat form (Perf H4: the
#    grouped einsum replicates the cache under GSPMD); with none it attends
#    grouped, to the same numbers
cfg6 = get_smoke_config("h2o-danube-1.8b")  # 4 heads over 2 kv; SWA ring
p6 = M.init_params(cfg6, jax.random.PRNGKey(5))
c6 = jax.tree.map(
    lambda a: jax.random.normal(jax.random.PRNGKey(6), a.shape).astype(
        a.dtype), M.init_cache(cfg6, 4, 32))
t6 = jnp.full((4, 1), 7, jnp.int32)
i6 = jnp.asarray([0, 3, 40, 31], jnp.int32)
step6 = jax.jit(lambda p, c: M.decode_step(p, cfg6, t6, c, i6))
group = "4x32x2x2x32"  # K/V repeated to (B, C, KV, G, hd)
assert group not in step6.lower(p6, c6).as_text()
ref6, _ = step6(p6, c6)
with use_context(context_for_mesh(mesh4)):
    step6m = jax.jit(lambda p, c: M.decode_step(p, cfg6, t6, c, i6))
    assert group in step6m.lower(p6, c6).as_text()
    out6, _ = step6m(p6, c6)
err6 = float(jnp.max(jnp.abs(out6 - ref6)))
assert err6 < 5e-2, f"mesh decode err {err6}"
print("MESH_REPEAT_OK", err6)
"""


@pytest.mark.slow
def test_multidevice_subprocess():
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert res.returncode == 0, res.stderr[-3000:]
    assert "EP_PARITY_OK" in res.stdout
    assert "MULTIPOD_TRAIN_OK" in res.stdout
    assert "ZERO1_SHARDING_OK" in res.stdout
    assert "FLASH_DECODE_OK" in res.stdout
    assert "SEQ_PARALLEL_OK" in res.stdout
    assert "MESH_REPEAT_OK" in res.stdout


@pytest.mark.slow
def test_dryrun_cell_subprocess():
    """One production-mesh dry-run cell end to end (512 fake devices)."""
    script = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=512'\n"
        "from repro.launch.dryrun import run_cell\n"
        "row = run_cell('olmo-1b', 'decode_32k', 'single')\n"
        "assert row['status'] == 'ok', row.get('error')\n"
        "assert row['hlo_flops'] > 0\n"
        "row2 = run_cell('olmo-1b', 'decode_32k', 'multi')\n"
        "assert row2['status'] == 'ok', row2.get('error')\n"
        "print('DRYRUN_OK', row['dominant'], row2['chips'])\n")
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert res.returncode == 0, res.stderr[-3000:]
    assert "DRYRUN_OK" in res.stdout
