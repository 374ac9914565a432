"""A whole run at a size the CPU holds, with the timed path broken
underneath: each fault must take `correct` to false. The chip check is
skipped; every other step of a run is the benchmark's own."""
import json
import time

import pytest

import faults
import small
from chipbench.bench import ROOT, cell_spec, run_cell


def steady_spec():
    """The steady cell as committed (settings and limits), with the
    model and the fleet cut to a CPU size."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    s = cell_spec("olmo1b-steady4k", bench)
    s["model_file"] = {"name": "small", "model": dict(small.MODEL),
                       "optimizer": dict(small.OPT)}
    s["traffic_params"] = dict(s["traffic_params"], regions=16,
                               pool_rows=256, draws_per_camera=256)
    return s


def run(spec, hooks=(), mode=None):
    return run_cell(spec, seed=2**33 + 7, seconds=1.0, trace=False,
                    t0=time.perf_counter(), hooks=hooks, mode=mode,
                    log=lambda *a: None, device_kind="TPU v5 lite")


def values(res):
    return {k: v["value"] for k, v in res["checks"].items()}


@pytest.fixture(scope="module")
def steady():
    return run(steady_spec(), mode="readings")


def test_steady_cell_is_correct_and_its_control_is_not(steady):
    assert steady["correct"]
    assert steady["attempted"] >= 1 and steady["failed"] == 0
    # the control, judged by the cell's own limits, is not correct
    assert steady["readings"]["verdict"]["control"] is False
    assert steady["readings"]["verdict"]["program"] is True


@pytest.mark.parametrize("fault", ["drift_score", "partial_fleet"])
def test_fault_fails_the_steady_cell(fault):
    res = run(steady_spec(), hooks=[faults.ALL[fault]])
    assert not res["correct"]


@pytest.fixture(scope="module")
def sound():
    return run(small.spec(), mode="readings")


def test_a_training_run_records_every_number(sound):
    got = values(sound)
    assert set(got) == {"train_loss_gap", "train_grad_gap",
                        "train_update_gap", "eval_acc_gap",
                        "serve_logit_gap", "drift_score_gap"}
    assert all(v is not None for v in got.values())
    # float32 eval and a served token that is the reference's best or
    # within bfloat16 rounding of it
    assert got["eval_acc_gap"] < 1e-4
    assert got["serve_logit_gap"] < 0.05
    ctl = sound["readings"]["control"]
    assert ctl["eval_acc_gap"] > 10 * got["eval_acc_gap"]
    assert ctl["serve_logit_gap"] > got["serve_logit_gap"]


@pytest.mark.parametrize("fault,number,floor", [
    ("unchanged_state", "train_update_gap", 0.999),
    ("eval_answer", "eval_acc_gap", 0.099),
    ("served_token", "serve_logit_gap", 0.5),
])
def test_fault_moves_its_number(sound, fault, number, floor):
    res = run(small.spec(), hooks=[faults.ALL[fault]])
    assert not res["correct"]
    assert values(res)[number] >= floor
    assert values(sound)[number] < floor


def test_half_batch_moves_the_training_numbers(sound):
    res = run(small.spec(), hooks=[faults.half_batch])
    assert not res["correct"]
    got, ref = values(res), sound["readings"]["half_batch"]
    for k in ("train_loss_gap", "train_grad_gap", "train_update_gap"):
        assert got[k] != values(sound)[k]
    # the reference with the same fault reads the same distance
    assert ref["train_loss_gap"] > 2 * values(sound)["train_loss_gap"] or \
        got["train_loss_gap"] > 2 * values(sound)["train_loss_gap"]
