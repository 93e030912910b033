"""Tests of the chip benchmark, on the CPU at tiny sizes.

They put the benchmark's directory and the program's ``src`` on the path,
as ``run.py`` does, and build tiny cells from the real ones: the registry's
smoke configs in place of the published widths, a few slots, short
traffic.
"""
import copy
import dataclasses
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))


def models_entry(cfg) -> dict:
    """A configuration file's ``models`` entry for a registry config."""
    return {
        "family": "dense_gqa",
        "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "num_hidden_layers": cfg.num_layers, "vocab_size": cfg.vocab_size,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "attention_bias": cfg.qkv_bias,
        "sliding_window": cfg.sliding_window,
    }


def tiny_cell(name: str = "cascade-chat", n_slots: int = 4,
              max_len: int = 128, limit: float = 0.05):
    """The cell ``name`` at a CPU size, with the smoke configs it runs."""
    from repro.configs import get_smoke_config
    from spec import load_cell
    cell = load_cell(name)
    cfgs = [dataclasses.replace(get_smoke_config(n), num_layers=2)
            for n in cell.config["stages"]]
    conf = copy.deepcopy(cell.config)
    conf.update(n_slots=n_slots, max_len=max_len, min_len_bucket=8)
    conf["models"] = {c.name: models_entry(c) for c in cfgs}
    conf["limits"] = {c.name: {"logit_shortfall": limit, "gap_error": limit}
                      for c in cfgs}
    mix = copy.deepcopy(cell.traffic)
    mix["prompt"].update(lo=4, hi=24)
    mix["output"].update(lo=4, hi=12)
    mix["id_hi"] = min(c.vocab_size for c in cfgs)
    mix["lead_in_s"] = 0.2
    mix["rate_rps"], mix["drain_s"] = 8.0, 20
    mix["prompt"]["median"] = 10
    mix["output"]["median"] = 6
    cell = dataclasses.replace(cell, config=conf, traffic=mix)
    return cell, cfgs


@pytest.fixture
def quiet():
    return lambda *a, **k: None
