"""Roofline extraction: loop-aware HLO cost model exactness + report math."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.profiling import hw
from repro.profiling.hlo_cost import analyze_hlo_text, parse_hlo
from repro.profiling.roofline import (RooflineReport,
                                      collective_bytes_from_hlo)


def test_matmul_flops_exact():
    def mm(a, b):
        return a @ b
    a = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    b = jax.ShapeDtypeStruct((512, 128), jnp.float32)
    c = jax.jit(mm).lower(a, b).compile()
    s = analyze_hlo_text(c.as_text())
    assert s.flops == pytest.approx(2 * 256 * 512 * 128, rel=0.01)


def test_scan_loop_trip_count_multiplies():
    """THE bug this module exists for: XLA cost_analysis counts while
    bodies once; ours multiplies by the derived trip count."""
    def scanned(x, w):
        def body(h, _):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, None, length=8)
        return h
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = jax.jit(scanned).lower(x, w).compile()
    xla = c.cost_analysis().get("flops", 0.0)
    ours = analyze_hlo_text(c.as_text()).flops
    true = 8 * 2 * 128 ** 3
    assert ours == pytest.approx(true, rel=0.01)
    assert xla < true / 4  # XLA undercounts (counts the body once)


def test_nested_scan():
    def nested(x, w):
        def outer(h, _):
            def inner(g, _):
                return g @ w, None
            g, _ = jax.lax.scan(inner, h, None, length=4)
            return g, None
        h, _ = jax.lax.scan(outer, x, None, length=3)
        return h
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    c = jax.jit(nested).lower(x, w).compile()
    s = analyze_hlo_text(c.as_text())
    assert s.flops == pytest.approx(12 * 2 * 64 ** 3, rel=0.01)


def test_collective_parser_synthetic_text():
    text = """
HloModule m

ENTRY %main (a: f32[1024]) -> f32[2048] {
  %a = f32[1024]{0} parameter(0)
  %ag = f32[2048]{0} all-gather(%a), replica_groups=[8,2]<=[16], dimensions={0}
  %ar = f32[2048]{0} all-reduce(%ag), replica_groups=[4,4]<=[16], to_apply=%add
  ROOT %rs = f32[1024]{0} reduce-scatter(%ar), replica_groups=[8,2]<=[16], dimensions={0}
}
"""
    out = collective_bytes_from_hlo(text)
    assert out["all-gather"] == 2048 * 4 // 2      # result / group
    assert out["all-reduce"] == 2048 * 4            # == result
    assert out["reduce-scatter"] == 1024 * 4 * 2    # result x group


def test_roofline_report_math():
    rep = RooflineReport(
        arch="x", shape="train_4k", mesh="single", chips=256,
        hlo_flops=1e12, hlo_bytes=1e10, collective_bytes=1e9,
        collective_breakdown={}, model_flops_total=200e12,
        model_bytes_total=1e12)
    assert rep.t_compute == pytest.approx(1e12 / hw.PEAK_FLOPS_BF16)
    assert rep.t_memory == pytest.approx(1e10 / hw.HBM_BW)
    assert rep.t_collective == pytest.approx(1e9 / hw.ICI_BW)
    assert rep.dominant == "collective"
    d = rep.to_dict()
    assert 0 < d["roofline_fraction"] <= 1.0 or d["roofline_fraction"] > 0


def test_parse_hlo_computations():
    text = """
HloModule m

%helper (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %t = f32[4]{0} tanh(%p)
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  ROOT %c = f32[4]{0} call(%x), to_apply=%helper
}
"""
    comps, entry = parse_hlo(text)
    assert entry == "main"
    assert "helper" in comps
    assert comps["helper"].instrs[-1].opcode == "tanh"


def test_dryrun_artifacts_complete():
    """The committed sweep artifacts must cover all 80 cells, error-free."""
    import json
    import os
    rows = []
    for f in ("benchmarks/artifacts/dryrun_single.json",
              "benchmarks/artifacts/dryrun_multi.json"):
        if os.path.exists(f):
            rows += json.load(open(f))
    if not rows:
        pytest.skip("dry-run artifacts not generated yet")
    assert len(rows) == 80
    assert sum(r["status"] == "ok" for r in rows) == 66
    assert sum(r["status"] == "skip" for r in rows) == 14
    assert not any(r["status"] == "error" for r in rows)
    for r in rows:
        if r["status"] == "ok":
            assert r["hlo_flops"] > 0
            assert r["hlo_bytes"] > 0
            assert r["dominant"] in ("compute", "memory", "collective")
