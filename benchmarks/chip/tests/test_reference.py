"""The dense family's plain reference against the program's own full
forward pass, on the same seeded float32 weights, at a tiny size on the
CPU."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import models_entry
from reference.ops import fake_quant
from spec import load_family
from weights import make_params

F = load_family("dense_gqa")


@pytest.mark.parametrize("name,seq", [("qwen2-0.5b", 40),
                                      ("h2o-danube-1.8b", 90)])
def test_reference_matches_program_forward(name, seq):
    from repro.configs import get_smoke_config
    from repro.models import model as M
    cfg = dataclasses.replace(get_smoke_config(name), num_layers=2)
    # danube's smoke window (64) is shorter than the sequence: the
    # reference's window mask is exercised
    a = F.arch(models_entry(cfg))
    params = make_params(M.init_params(cfg, spec_only=True,
                                       dtype=jnp.float32), 12345, 0)
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, seq) \
        .astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = M.forward(params, cfg, {"tokens": toks[None]})
    x = F.hidden(a, params, toks)
    target = np.zeros(x.shape[0], np.int32)
    short, gap, pick = F.stats(a, params, x, target)
    w = np.asarray(want[0], np.float64)
    top2 = np.sort(w, -1)[:, -2:]
    np.testing.assert_array_equal(pick[:seq], w.argmax(-1))
    np.testing.assert_allclose(gap[:seq], top2[:, 1] - top2[:, 0],
                               atol=2e-5)
    np.testing.assert_allclose(short[:seq], w.max(-1) - w[:, 0], atol=2e-5)


def test_int8_and_fp8_controls_round():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(8, 64)),
                    jnp.float32)
    for q, step in (("int8", 1 / 127), ("fp8", 1 / 8)):
        y = fake_quant(x, -1, q)
        rel = jnp.abs(y - x).max() / jnp.abs(x).max()
        assert 0 < float(rel) <= step
