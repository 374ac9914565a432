"""The harness reaches a model only through the architecture module its
configuration file names (`chipbench/arch/`): the dense module's
numbers, its layout against the program's, the calls a run makes
through it, nested model settings, and an unknown name."""
import collections
import copy
import functools
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

import small
from chipbench import reference, weights
from chipbench.arch import dense
from chipbench.bench import (HERE, ROOT, cell_spec, load_arch,
                             program_config, run_cell)

SEED = 2**33 + 5

# The small dense model's numbers at SEED, recorded when the dense
# parts still lived in weights.py and reference.py (float64 sums on the
# host of the float32 values).
LEAF_SUMS = {
    "['embed']['table']": 2.7551861087548417,
    "['embed']['unembed']": -3.283636289620972,
    "['final_norm']['bias']": 0.0,
    "['final_norm']['scale']": 64.0,
    "['segments'][0]['attn']['wk']": 37.11110896684113,
    "['segments'][0]['attn']['wo']": -4.168354782646929,
    "['segments'][0]['attn']['wq']": 22.220779611263424,
    "['segments'][0]['attn']['wv']": 3.0844080954848323,
    "['segments'][0]['ln1']['bias']": 0.0,
    "['segments'][0]['ln1']['scale']": 64.0,
    "['segments'][0]['ln2']['bias']": 0.0,
    "['segments'][0]['ln2']['scale']": 64.0,
    "['segments'][0]['mlp']['w_down']": -1.8074965768239792,
    "['segments'][0]['mlp']['w_gate']": 1.5646697795127693,
    "['segments'][0]['mlp']['w_up']": -14.169652535045316}
LOGIT_SQUARES = 1572.7200009328037
LOGIT_ABS = 8108.396852244296
LOSSES = [5.575161933898926, 5.565677642822266, 5.573294162750244]


def test_the_dense_module_reproduces_the_recorded_numbers():
    model, opt = dict(small.MODEL), dict(small.OPT)
    params = weights.make_params(dense, model, SEED)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    sums = {jax.tree_util.keystr(k): float(np.asarray(v, np.float64).sum())
            for k, v in flat}
    assert sums == pytest.approx(LEAF_SUMS, rel=1e-6)
    toks = np.random.default_rng(7).integers(0, 256, (3, 8, 32))
    lg = np.asarray(reference.logits(dense, model, params, toks[0]),
                    np.float64)
    assert (lg ** 2).sum() == pytest.approx(LOGIT_SQUARES, rel=1e-6)
    assert np.abs(lg).sum() == pytest.approx(LOGIT_ABS, rel=1e-6)
    got = reference.train(dense, model, opt, params, list(toks))
    assert got["loss"] == pytest.approx(LOSSES, rel=1e-6)


def _is_shape(x):
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def _layout(tree, is_leaf=None):
    """(tree structure, {leaf path: shape}) of a tree of shapes or of
    arrays."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)
    return (jax.tree.structure(jax.tree.map(lambda _: 0, tree,
                                            is_leaf=is_leaf)),
            {jax.tree_util.keystr(p): tuple(getattr(x, "shape", x))
             for p, x in flat})


MODEL_FILES = {p.stem: json.loads(p.read_text())
               for p in sorted((HERE / "configs").glob("*.json"))}
MODEL_FILES["small"] = small.spec()["model_file"]


@pytest.mark.parametrize("name", sorted(MODEL_FILES))
def test_architecture_layout_is_the_programs(name):
    """At the file's own widths (shapes only: nothing is allocated), the
    architecture's tree and its initialiser's output have the program's
    parameter layout, as `train_step.init_state` builds it."""
    from repro.models.model import build_model
    from repro.train.train_step import init_state

    mf = MODEL_FILES[name]
    arch, model = load_arch(mf["arch"]), mf["model"]
    key = jax.random.PRNGKey(0)
    prog = jax.eval_shape(
        lambda k: init_state(build_model(program_config(mf)), k),
        key)["params"]
    want = _layout(prog)
    assert _layout(arch.shapes(model), _is_shape) == want
    made = jax.eval_shape(functools.partial(arch.init_params, model), key)
    assert _layout(made) == want
    assert {x.dtype for x in jax.tree.leaves(made)} == \
        {x.dtype for x in jax.tree.leaves(prog)}


class Spy:
    """An architecture that is the dense one, counting each call of each
    of its functions."""

    def __init__(self):
        self.calls = collections.Counter()

    def __getattr__(self, name):
        fn = getattr(dense, name)

        def counted(*a, **kw):
            self.calls[name] += 1
            return fn(*a, **kw)
        return counted


def retrace_decode_run(ctl):
    """The serve plane's run of ticks traced afresh at every launch, as a
    retrace inside the timed loop would be."""
    from repro.serve.serve_step import make_fleet_decode_run
    plane = ctl.serve_plane
    make = functools.partial(make_fleet_decode_run,
                             lambda *a: plane._fleet_decode(*a),
                             plane.scfg.max_new)
    plane._fleet_decode_run = lambda *a: jax.jit(make())(*a)


def one_window(arch, hooks=()):
    """The small serving cell with `arch` in the dense module's place,
    timed over exactly one window (so that two runs check the same
    window)."""
    spec = small.spec()
    spec["arch"] = arch
    return run_cell(spec, seed=SEED, seconds=0.0, trace=False, t0=0.0,
                    hooks=hooks, log=lambda *_: None,
                    device_kind="TPU v5 lite")


@pytest.fixture(scope="module")
def dense_run():
    return one_window(dense)


def test_a_run_reaches_the_model_only_through_its_architecture(dense_run):
    spy = Spy()
    res = one_window(spy)
    # every number the check compares, bit for bit (the small cell
    # trains, so the program's training fault fails its limits alike)
    assert res["checks"] == dense_run["checks"]
    assert res["correct"] == dense_run["correct"]
    # initialisation, the check's forward passes (the reference's
    # logits and its training steps), the FLOP tally of the timed
    # window: training, eval and prefill, and decode
    for name in ("init_params", "forward", "forward_flops",
                 "decode_flops"):
        assert spy.calls[name] > 0, name


def test_the_decode_tally_survives_a_retrace_in_the_timed_window(
        dense_run):
    spy = Spy()
    res = one_window(spy, hooks=[retrace_decode_run])
    assert res["checks"] == dense_run["checks"]
    assert spy.calls["decode_flops"] > 0


def test_nested_model_settings_become_the_programs_config():
    from repro.configs.base import ModelConfig, MoEConfig, SSMConfig

    moe = {"num_experts": 8, "top_k": 2, "d_ff_expert": 32,
           "num_shared_experts": 1, "d_ff_shared": 64}
    model = dict(small.MODEL, family="moe", moe=moe,
                 ssm={"state_dim": 8, "conv_width": 4, "expand": 2},
                 sliding_window=16, global_attn_layers=[0, 2])
    got = program_config({"name": "nested", "model": model})
    assert got == ModelConfig(
        name="nested", **dict(small.MODEL, family="moe"),
        moe=MoEConfig(8, 2, 32, num_shared_experts=1, d_ff_shared=64),
        ssm=SSMConfig(state_dim=8, conv_width=4, expand=2),
        sliding_window=16, global_attn_layers=(0, 2))
    other = copy.deepcopy(model)
    other["moe"]["top_k"] = 4
    frozen = weights.hashable(model)
    assert frozen != weights.hashable(other)
    assert hash(frozen) != hash(weights.hashable(other))
    assert weights.hashable(copy.deepcopy(model)) == frozen
    # what an architecture module sees inside the jit: the whole model
    assert weights.thaw(frozen) == model


@pytest.mark.parametrize("name", [None, "nope", "../bench", "Dense",
                                  "__init__"])
def test_an_unknown_architecture_is_a_setup_error(name, tmp_path,
                                                  monkeypatch):
    with pytest.raises(KeyError):
        load_arch(name)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = bench["workloads"][0]
    mf = dict(MODEL_FILES[cell["config"]])
    if name is None:
        mf.pop("arch")
    else:
        mf["arch"] = name
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "chipbench" / "configs" / f"{cell['config']}.json") \
        .write_text(json.dumps(mf))
    monkeypatch.setattr("chipbench.bench.HERE", tmp_path / "chipbench")
    with pytest.raises(KeyError):
        cell_spec(cell["name"], bench)
    # the command exits 2, as for an unknown workload, with no result
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", cell["name"],
         "--seed", "1", "--seconds", "1"], cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert p.returncode == 2, p.stderr
    assert "no architecture" in p.stderr
    assert '"correct"' not in p.stdout
