"""The comparison that decides ``correct``: its arithmetic, and its control
— the reference computed a precision lower must fail the limits a sound
program passes, here at a size a test can hold (the chip readings at the
cells' own size are in PERF.md)."""

import json
import math
import os

import numpy as np
import pytest

from benchmark import check

HERE = os.path.dirname(os.path.abspath(__file__))
OVERLAY = os.path.join(HERE, "fixtures", "overlay", "benchmark", "configs")


def test_benchmark_worst_leaf_gap_is_of_norms_against_the_larger_scale():
    reference = {"a": 10.0, "b": 1.0, "c": 1e-9}
    program = {"a": 10.5, "b": 1.0, "c": 2e-9}
    gap, leaf = check.worst_leaf_gap(program, reference)
    # "c" doubles but is all but zero: held against the median leaf (1.0)
    assert leaf == "a" and gap == pytest.approx(0.05)
    gap, leaf = check.worst_leaf_gap(dict(program, b=1.2), reference)
    assert leaf == "b" and gap == pytest.approx(0.2)
    with pytest.raises(ValueError):
        check.worst_leaf_gap({"a": 1.0}, reference)
    gap, _ = check.worst_leaf_gap(dict(program, b=math.nan), reference)
    assert not gap <= 1e9


def test_benchmark_numbers_and_limits():
    reference = {"losses": [2.0, 1.0, 0.5], "grad_norms": {"w": 4.0},
                 "change_norms": {"w": 0.1}}
    program = {"losses": [2.02, 1.0, 0.5], "grad_norms": {"w": 4.4},
               "change_norms": {"w": 0.1}}
    numbers = check.numbers(program, reference)
    assert numbers["loss_step1_rel"] == pytest.approx(0.01)
    assert numbers["first_grad_norm_gap"] == pytest.approx(0.1)
    rows = {r["name"]: r for r in check.judge(numbers, {
        "loss_step1_rel": 0.02, "first_grad_norm_gap": 0.05,
        "never_computed": 1.0})}
    assert rows["loss_step1_rel"]["ok"]
    assert not rows["first_grad_norm_gap"]["ok"]
    assert not rows["never_computed"]["ok"]     # a limit without a number


def test_benchmark_rows_are_accounted_for_by_their_numbers():
    one_pass = np.random.default_rng(0).permutation(100)
    sound = [one_pass[:40], one_pass[40:], one_pass[:30]]
    assert check.epoch_accounting(sound, 100, same_order=True) == {
        "rows_seen": 130, "passes_complete": 1, "bad_rows": 0}
    shuffled = [one_pass, np.arange(100), np.arange(7)]
    assert check.epoch_accounting(shuffled, 100, False)["bad_rows"] == 0
    assert check.epoch_accounting(shuffled, 100, True)["bad_rows"] > 0
    lost = [np.r_[one_pass[:99], one_pass[0]]]          # one row twice
    assert check.epoch_accounting(lost, 100, False)["bad_rows"] == 1
    assert check.epoch_accounting([np.array([100])], 100, False)["bad_rows"]
    assert check.epoch_accounting([], 100, True)["rows_seen"] == 0


def _fixture(name):
    with open(os.path.join(OVERLAY, name, "config.json")) as f:
        config = json.load(f)
    with open(os.path.join(OVERLAY, name, "limits.json")) as f:
        return config, json.load(f)["limits"]


CASES = {
    "resnet_tiny": ("benchmark.configs.resnet50", "imagenet_records",
                    {"image_side": 16, "classes": 10}, 8),
    "widedeep_tiny": ("benchmark.configs.criteo_widedeep", "criteo_rows",
                      {"rows": 96, "hash_buckets": 50, "zipf_s": 1.05}, 32),
}


@pytest.mark.parametrize("name", sorted(CASES))
@pytest.mark.parametrize("seed", [2 ** 31 + 3, 17, 991])
def test_benchmark_control_fails_where_the_sound_program_passes(name, seed):
    import importlib

    import jax

    from benchmark import control

    package, generator, params, batch = CASES[name]
    program = importlib.import_module(package + ".program")
    reference = importlib.import_module(package + ".reference")
    gen = importlib.import_module("benchmark.traffic." + generator)
    config, limits = _fixture(name)
    batches = [gen.rows(params, seed, range(i * batch, (i + 1) * batch))
               for i in range(3)]
    theirs = reference.follow(config, seed, batches)
    mine = control.program_numbers(jax, {"config_values": config}, program,
                                   reference, batches, seed)
    sound = check.judge(check.numbers(mine, theirs), limits)
    assert all(r["ok"] for r in sound), sound
    lowered = reference.follow(config, seed, batches,
                               lower=config["control_precision"])
    control = check.judge(check.numbers(lowered, theirs), limits)
    assert not all(r["ok"] for r in control), control
