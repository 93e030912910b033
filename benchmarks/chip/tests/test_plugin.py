"""A configuration of a new architecture is added by new files and entries
alone: a toy family (a routed-expert toy model, with its counts and its
plain reference), its configuration file and one workload, put beside a
copy of the benchmark. Nothing that is there is edited."""
import json
import shutil
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import harness
import spec
from driver import Call
from reference.compare import compare

CHIP = Path(__file__).resolve().parents[1]
REPO = CHIP.parents[1]

TOY_FAMILY = '''"""Toy routed-expert model: logits = RMSNorm(causal mean
of the token embeddings) E^T. A decode step reads the head and the experts
its tokens route to, which only the engine can count (``call.counts``)."""
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from costs import WEIGHT_BYTES
from reference.ops import mm, rms


@dataclass(frozen=True)
class Arch:
    vocab: int
    d_model: int
    experts: int
    eps: float


def arch(entry):
    return Arch(int(entry["vocab_size"]), int(entry["hidden_size"]),
                int(entry["num_experts"]), float(entry["rms_norm_eps"]))


def stated(a):
    return (a.vocab, a.d_model, a.experts)


def served(cfg):
    return (cfg.vocab_size, cfg.d_model, cfg.num_experts)


def decode_call(a, call):
    rows = len(call.depths)
    experts = call.counts.get("experts_read", 0)
    flops = 2.0 * a.vocab * a.d_model * rows
    nbytes = (a.vocab * a.d_model + experts * a.d_model * a.d_model) \\
        * WEIGHT_BYTES
    return flops, nbytes


def call_flops(a, call):
    n = sum(call.prompt_lens) if call.kind == "admit" else len(call.depths)
    return 2.0 * a.vocab * a.d_model * n


def hidden(a, params, tokens, quant=None):
    e = jnp.asarray(params["embed"], jnp.float32)[np.asarray(tokens)]
    return jnp.cumsum(e, 0) / jnp.arange(1, len(tokens) + 1)[:, None]


def stats(a, params, x, target, quant=None):
    logits = mm(rms(x, 1.0, a.eps), jnp.asarray(params["embed"]).T, quant)
    top2 = np.sort(np.asarray(logits), -1)[:, -2:]
    tgt = np.take_along_axis(np.asarray(logits),
                             np.clip(target, 0, None)[:, None], 1)[:, 0]
    return (top2[:, 1] - tgt, top2[:, 1] - top2[:, 0],
            np.asarray(logits).argmax(-1).astype(np.int32))
'''


@pytest.fixture
def root(tmp_path):
    """A copy of the benchmark with the toy family, its configuration and
    a workload added as new files and entries."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    chip = tmp_path / "benchmarks/chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns("__pycache__"))
    (chip / "families/toy_experts.py").write_text(TOY_FAMILY)
    conf = {"name": "toy", "stages": ["toy"], "n_slots": 2, "max_len": 64,
            "min_len_bucket": 8, "escalation_target": 0.5,
            "weights_seed": 1,
            "models": {"toy": {"family": "toy_experts", "vocab_size": 64,
                               "hidden_size": 8, "num_experts": 4,
                               "rms_norm_eps": 1e-6}},
            "limits": {"toy": {"logit_shortfall": 1e-4,
                               "gap_error": 1e-4}}}
    (chip / "configs/toy.json").write_text(json.dumps(conf))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "toy", "source": "https://example.org/toy",
        "file": "benchmarks/chip/configs/toy.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "toy-chat", "config": "toy", "traffic": "chat-cascade",
        "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for path in CHIP.rglob("*"):
        if path.is_file() and "__pycache__" not in path.parts:
            copy = chip / path.relative_to(CHIP)
            assert copy.read_bytes() == path.read_bytes(), path
    return tmp_path


def toy_cfg(**kw):
    return SimpleNamespace(**{"name": "toy", "vocab_size": 64, "d_model": 8,
                              "num_experts": 4, **kw})


def test_load_cell_finds_the_family(root):
    cell = spec.load_cell("toy-chat", root=root)
    fam = cell.family("toy")
    assert Path(fam.__file__) == (root / "benchmarks/chip/families"
                                  / "toy_experts.py").resolve()
    assert fam.arch(cell.config["models"]["toy"]).vocab == 64
    # the cells that were there load as before, on the dense family
    old = spec.load_cell("cascade-chat", root=root)
    assert old.family("qwen2-0.5b").__name__ != fam.__name__


def test_stage_configs_compares_the_familys_shapes(root):
    cell = spec.load_cell("toy-chat", root=root)
    assert harness.stage_configs(cell, [toy_cfg()])[0].name == "toy"
    with pytest.raises(ValueError, match="the configuration file states"):
        harness.stage_configs(cell, [toy_cfg(d_model=16)])


def test_decode_call_reads_the_calls_counts(root):
    fam = spec.load_cell("toy-chat", root=root).family("toy")
    a = fam.Arch(vocab=64, d_model=8, experts=4, eps=1e-6)
    reads = [fam.decode_call(a, Call("decode", 0, 0.0, 1.0, 2,
                                     depths=[3, 4],
                                     counts={"experts_read": n}))
             for n in (1, 3)]
    assert reads[0][0] == reads[1][0] == 2.0 * 64 * 8 * 2
    assert reads[1][1] - reads[0][1] == 2 * 8 * 8 * 2


def test_compare_scores_served_tokens_against_the_toy_reference(root):
    fam = spec.load_cell("toy-chat", root=root).family("toy")
    a = fam.Arch(vocab=64, d_model=8, experts=4, eps=1e-6)
    params = {"embed": np.random.default_rng(3).normal(size=(64, 8))
              .astype(np.float32)}
    prompt = np.array([5, 9, 2, 33], np.int32)
    seq, tokens, gaps = list(prompt), [], []
    for _ in range(6):          # greedy, by the reference itself
        x = fam.hidden(a, params, np.asarray(seq, np.int32))
        _, gap, pick = fam.stats(a, params, x, np.zeros(len(seq), np.int32))
        tokens.append(int(pick[-1]))
        gaps.append(float(gap[-1]))
        seq.append(tokens[-1])

    def score(toks):
        done = {0: [{"rid": 0, "prompt": prompt, "tokens": toks,
                     "gaps": gaps}]}
        return compare(["toy"], {"toy": (fam, a)}, [params], done, 7,
                       min_tokens=1, max_requests=1)["toy"]

    same = score(tokens)
    assert same["tokens"] == 6 and same["requests"] == 1
    assert same["logit_shortfall"] == 0.0
    assert same["gap_error"] < 1e-5
    altered = list(tokens)
    altered[2] = (altered[2] + 1) % 64
    assert score(altered)["logit_shortfall"] > 1e-3


@pytest.mark.parametrize("family", ["nope", None])
def test_an_unknown_or_missing_family_fails_at_load(root, family):
    path = root / "benchmarks/chip/configs/toy.json"
    conf = json.loads(path.read_text())
    if family is None:
        del conf["models"]["toy"]["family"]
    else:
        conf["models"]["toy"]["family"] = family
    path.write_text(json.dumps(conf))
    with pytest.raises(KeyError, match="dense_gqa.*toy_experts"):
        spec.load_cell("toy-chat", root=root)
