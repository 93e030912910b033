"""Per-architecture smoke tests (assignment requirement): reduced config,
one forward/train step on CPU, output shapes + no NaNs; plus prefill/decode
consistency against the full forward."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke_config
from repro.models import attention as A
from repro.models import model as M
from repro.models.common import ArrayFactory, apply_rope


def make_batch(cfg, b=2, s=24, with_labels=True, rng=0):
    key = jax.random.PRNGKey(rng)
    batch = {"tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size)}
    if with_labels:
        batch["labels"] = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    if cfg.frontend.kind == "vision":
        batch["prefix_embeddings"] = jnp.ones(
            (b, cfg.frontend.num_prefix_embeddings,
             cfg.frontend.frontend_dim), jnp.bfloat16)
    if cfg.is_encoder_decoder:
        batch["source_frames"] = jax.random.normal(
            key, (b, 16, cfg.frontend.frontend_dim or cfg.d_model)
        ).astype(jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_train_step(arch):
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg)
    logits, aux = M.forward(params, cfg, batch)
    s_tot = 24 + (cfg.frontend.num_prefix_embeddings
                  if cfg.frontend.kind == "vision" else 0)
    assert logits.shape == (2, s_tot, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    loss, metrics = M.train_loss(params, cfg, batch, remat=True)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss))
    grads = jax.grad(lambda p: M.train_loss(p, cfg, batch, remat=True)[0]
                     )(params)
    gleaves = jax.tree.leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
               for g in gleaves)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_shapes(arch):
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    b, s = 2, 16
    batch = make_batch(cfg, b, s, with_labels=False)
    # the cache must cover the FULL prompt incl. any modality prefix
    # (prefill validates this since the cache_len sentinel fix)
    s_tot = s + (cfg.frontend.num_prefix_embeddings
                 if cfg.frontend.kind == "vision" else 0)
    logits, cache = M.prefill(params, cfg, batch, cache_len=s_tot + 4)
    assert logits.shape == (b, cfg.vocab_size)
    tok = jnp.zeros((b, 1), jnp.int32)
    dlogits, cache2 = M.decode_step(params, cfg, tok, cache,
                                    jnp.asarray(s_tot, jnp.int32))
    assert dlogits.shape == (b, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(dlogits)))
    # cache structure preserved
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "h2o-danube-1.8b",
                                  "falcon-mamba-7b", "seamless-m4t-large-v2",
                                  "olmo-1b", "qwen3-32b"])
def test_decode_matches_forward(arch):
    """Teacher-forced decode equals the full forward (exact for non-MoE)."""
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    b, s = 2, 13
    toks = jax.random.randint(jax.random.PRNGKey(2), (b, s + 1), 0,
                              cfg.vocab_size)
    full = make_batch(cfg, b, s + 1, with_labels=False, rng=3)
    full["tokens"] = toks
    pre = dict(full)
    pre["tokens"] = toks[:, :s]
    logits_full, _ = M.forward(params, cfg, full)
    logits_pre, cache = M.prefill(params, cfg, pre, cache_len=s + 1)
    np.testing.assert_allclose(np.asarray(logits_pre),
                               np.asarray(logits_full[:, s - 1]),
                               atol=2e-2, rtol=0)
    dl, _ = M.decode_step(params, cfg, toks[:, s:s + 1], cache,
                          jnp.asarray(s, jnp.int32))
    np.testing.assert_allclose(np.asarray(dl),
                               np.asarray(logits_full[:, s]),
                               atol=5e-2, rtol=0)


def test_sliding_window_ring_buffer():
    """Danube's SWA ring cache: decode past the window matches forward."""
    cfg = get_smoke_config("h2o-danube-1.8b")
    assert cfg.sliding_window == 64
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    b, s = 1, 80  # past the 64-token window
    toks = jax.random.randint(jax.random.PRNGKey(2), (b, s + 1), 0,
                              cfg.vocab_size)
    logits_full, _ = M.forward(params, cfg, {"tokens": toks})
    _, cache = M.prefill(params, cfg, {"tokens": toks[:, :s]},
                         cache_len=s + 1)
    assert cache["blocks"][0]["k"].shape[2] == cfg.sliding_window
    dl, _ = M.decode_step(params, cfg, toks[:, s:s + 1], cache,
                          jnp.asarray(s, jnp.int32))
    np.testing.assert_allclose(np.asarray(dl), np.asarray(logits_full[:, s]),
                               atol=5e-2, rtol=0)


def test_block_pattern_structure():
    from repro.models.model import block_pattern
    from repro.configs import get_config
    jamba = block_pattern(get_config("jamba-v0.1-52b"))
    assert len(jamba) == 8
    assert [sp.mixer for sp in jamba].count("attn") == 1
    assert jamba[4].mixer == "attn"
    assert [sp.ffn for sp in jamba].count("moe") == 4
    llama4 = block_pattern(get_config("llama4-maverick-400b-a17b"))
    assert [sp.ffn for sp in llama4] == ["dense", "moe"]


@pytest.mark.parametrize("arch,heads,index", [
    ("qwen2-0.5b", 8, [0, 5, 17, 31]),        # ragged (B,) depths
    ("qwen2-0.5b", 8, 11),                    # one depth for the batch
    ("h2o-danube-1.8b", 8, [3, 63, 64, 100]),  # ring buffer, rows past wrap
    ("olmo-1b", 4, [0, 9, 20, 31]),            # G == 1 (MHA)
], ids=["ragged", "scalar", "ring", "mha"])
def test_grouped_decode_matches_repeat_form(arch, heads, index):
    """One-device decode attends grouped over the bf16 cache; it must give
    what the repeat form gives on the same written cache and mask."""
    cfg = dataclasses.replace(get_smoke_config(arch), num_heads=heads)
    g = cfg.num_heads // cfg.num_kv_heads
    assert g == (1 if arch == "olmo-1b" else 4)
    p = A.make_attention_params(ArrayFactory(jax.random.PRNGKey(0), False),
                                cfg)
    b, c_len = 4, A.kv_cache_len(cfg, 128 if cfg.sliding_window else 32)
    shape = (b, c_len, cfg.num_kv_heads, cfg.head_dim)
    cache = {n: jax.random.normal(jax.random.PRNGKey(i + 1), shape
                                  ).astype(jnp.bfloat16)
             for i, n in enumerate("kv")}
    x = jax.random.normal(jax.random.PRNGKey(3), (b, 1, cfg.d_model)
                          ).astype(jnp.bfloat16)
    idx = jnp.asarray(index, jnp.int32)

    step = jax.jit(lambda c: A.decode_attention(p, cfg, x, c, idx))
    out, new = step(cache)
    hlo = step.lower(cache).as_text()
    assert f"{b}x{c_len}x{cfg.num_kv_heads}x{g}x{cfg.head_dim}" not in hlo

    depth = jnp.broadcast_to(idx, (b,))
    q, _, _ = A._project_qkv(p, cfg, x)
    q = apply_rope(q, depth[:, None], cfg.rope_theta)
    pos = jnp.arange(c_len)[None, :]
    if cfg.sliding_window:
        valid = (pos <= depth[:, None] % c_len) | (depth[:, None] >= c_len)
    else:
        valid = pos <= depth[:, None]
    ref = A.sdpa(q, A._repeat_kv(new["k"], cfg.num_heads),
                 A._repeat_kv(new["v"], cfg.num_heads),
                 valid[:, None, None, :])
    ref = ref.reshape(b, 1, cfg.num_heads * cfg.head_dim) @ p["wo"]
    # same f32 scores and softmax, bf16 probabilities, bf16 PV and output
    # projection: the forms may differ by an ulp or two of bf16 at the
    # output's scale
    ref32 = np.asarray(ref.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)), ref32,
                               rtol=0, atol=4 * 2.0 ** -8
                               * float(np.max(np.abs(ref32))))


def _ragged_depths(cfg):
    """Per-row context depths for one ragged decode; row 1 is inactive,
    pinned to position 0. A ring cache gets a row past its wrap; MoE rows
    stay under 8 tokens, where capacity routing drops none."""
    if cfg.sliding_window:
        return [5, 0, 17, cfg.sliding_window + 6]
    if cfg.moe is not None:
        return [5, 0, 2, 7]
    return [5, 0, 17, 30]


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_ragged_decode_writes_only_its_rows(arch):
    """One ragged decode step over rows at mixed depths: each row's logits
    are what it gets decoded alone and what the full forward gives, and
    the step changes no attention-cache entry but each row's new slot in
    each layer."""
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    depths = _ragged_depths(cfg)
    b, n_prefix = len(depths), 0
    if cfg.frontend.kind == "vision":
        n_prefix = cfg.frontend.num_prefix_embeddings
    c_len = n_prefix + max(depths) + 2
    toks = jax.random.randint(jax.random.PRNGKey(2), (b, max(depths) + 1),
                              0, cfg.vocab_size)
    lanes, index, want = [], [], []
    for i, d in enumerate(depths):
        extra = {k: v[i:i + 1] for k, v in make_batch(
            cfg, b, 1, with_labels=False, rng=3).items() if k != "tokens"}
        if d == 0:
            # an idle lane holds a stale request's cache, and the row
            # decodes its token with no prefix
            extra.pop("prefix_embeddings", None)
            prompt, seen, at = toks[i:i + 1, :9], toks[i:i + 1, :1], 0
        else:
            prompt, seen = toks[i:i + 1, :d], toks[i:i + 1, :d + 1]
            at = d + (n_prefix if "prefix_embeddings" in extra else 0)
        _, lane = M.prefill(params, cfg, {**extra, "tokens": prompt},
                            cache_len=c_len)
        if d == 0:
            # SSM state is not masked by position: an idle row's is fresh
            lane["blocks"] = [
                {n: jnp.zeros_like(a) if n in ("conv", "ssm") else a
                 for n, a in c.items()} for c in lane["blocks"]]
        full, _ = M.forward(params, cfg, {**extra, "tokens": seen})
        lanes.append(lane)
        index.append(at)
        want.append(full[0, at])
    cache = jax.tree.map(lambda *a: jnp.concatenate(a, axis=1), *lanes)
    index = jnp.asarray(index, jnp.int32)
    step = toks[jnp.arange(b), jnp.asarray(depths)][:, None]
    logits, new = M.decode_step(params, cfg, step, cache, index)

    for i in range(b):
        alone, _ = M.decode_step(params, cfg, step[i:i + 1], lanes[i],
                                 index[i])
        np.testing.assert_allclose(np.asarray(logits[i]),
                                   np.asarray(alone[0]), atol=2e-2, rtol=0)
        np.testing.assert_allclose(np.asarray(logits[i]),
                                   np.asarray(want[i]), atol=5e-2, rtol=0)

    for spec, old, upd in zip(M.block_pattern(cfg), cache["blocks"],
                              new["blocks"]):
        if spec.mixer != "attn":
            continue
        ring = old["k"].shape[2]
        kept = np.ones((b, ring), bool)
        kept[np.arange(b), np.asarray(index) % ring] = False
        for n in ("k", "v"):  # bit for bit
            np.testing.assert_array_equal(
                np.asarray(upd[n]).view(np.uint16)[:, kept],
                np.asarray(old[n]).view(np.uint16)[:, kept])
