"""``BENCHMARK.json`` against the contract's static rules, and every name in
it against the files that carry it.  Cells, configurations and metrics are
found by reading the data files, so a later PR's additions are tested here
without an edit."""

import copy
import glob
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import spec  # noqa: E402


@pytest.fixture(scope="module")
def loaded():
    return spec.load(REPO)


def test_benchmark_json_meets_the_contract(loaded):
    spec.validate(loaded)
    spec.validate_files(loaded)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_benchmark_command_stays_inside_paths(loaded):
    program = loaded["command"][-1]
    assert any(program.startswith(p + "/") for p in loaded["paths"])
    assert os.path.isfile(os.path.join(REPO, program))


def _names():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("workload", [w["name"] for w in _names()["workloads"]])
def test_benchmark_cell_names_files_that_exist(loaded, workload):
    cell = spec.cell(loaded, workload)
    traffic = cell["traffic_values"]
    for module in (("traffic", traffic["generator"]), ("feeds", traffic["feed"])):
        mod = spec.module(cell["package"], *module)
        assert mod is not None
    for attr, name in (("generate", "traffic"), ("rows", "traffic")):
        assert callable(getattr(
            spec.module(cell["package"], name, traffic["generator"]), attr))
    feed = spec.module(cell["package"], "feeds", traffic["feed"])
    assert callable(feed.drive) and callable(feed.open_feed)
    for needed in ("batch_per_chip", "warmup_steps", "trace_after_steps",
                   "trace_steps", "max_epochs"):
        assert needed in traffic, needed
    config = cell["config_values"]
    assert config["control_precision"] in ("float8", "bfloat16")
    for key in cell["config_entry"]["reduced"]:
        assert key in config["reduced"], key
    config_dir = os.path.join(REPO, os.path.dirname(cell["config_entry"]["file"]))
    with open(os.path.join(config_dir, "limits.json")) as f:
        limits = json.load(f)
    assert set(limits["limits"]) == {
        "loss_step1_rel", "loss_step2_rel", "loss_step3_rel",
        "first_grad_norm_gap", "param_change_norm_gap"}
    assert limits["readings"]


@pytest.mark.parametrize("metric", [m["name"] for m in _names()["per_layer"]])
def test_benchmark_per_layer_metric_has_a_reader_of_its_own(loaded, metric):
    reader = spec.module(loaded, "metrics", metric)
    assert callable(reader.read)
    assert reader.__doc__, "a reader says what it reads"


def test_benchmark_every_metric_file_is_named_in_the_json(loaded):
    package = spec.package_of(loaded)
    files = {os.path.basename(p)[:-3] for p in glob.glob(
        os.path.join(REPO, package, "metrics", "*.py"))} - {"__init__"}
    assert files == {m["name"] for m in loaded["per_layer"]}


def _break(loaded, how):
    broken = copy.deepcopy(loaded)
    how(broken)
    return broken


BREAKS = {
    "bound over 0.1": lambda s: s["end_to_end"][1].update(bound=0.2),
    "no setup_s": lambda s: s["end_to_end"].pop(0),
    "unit with a space": lambda s: s["end_to_end"][1].update(unit="per s"),
    "a why on a metric": lambda s: s["per_layer"][0].update(why="x"),
    "reduced names a width": lambda s: s["configs"][0].update(
        reduced=["hidden_size"]),
    "reduced names a _dim": lambda s: s["configs"][0].update(
        reduced=["head_dim"]),
    "three chips": lambda s: s["workloads"][0].update(chips=3),
    "pair appears twice": lambda s: s["workloads"].append(
        dict(s["workloads"][0], name="again")),
    "moves an unknown metric": lambda s: s["per_layer"][0].update(
        moves="nothing"),
    "path leaves the repo": lambda s: s.update(paths=["../elsewhere"]),
    "absolute command word": lambda s: s["command"].append("/bin/x"),
    "run_seconds too long": lambda s: s.update(run_seconds=52),
    "name with a slash": lambda s: s["workloads"][0].update(name="a/b"),
    "config file outside paths": lambda s: s["configs"][0].update(
        file="elsewhere/config.json"),
    "program_span end to end": lambda s: s["end_to_end"][1].update(
        source="program_span"),
    "extra top-level key": lambda s: s.update(extra=1),
    "too many four-chip cells": lambda s: [w.update(chips=4)
                                           for w in s["workloads"]],
}


def test_benchmark_depth_may_be_reduced_and_widths_may_not():
    for key in ("num_hidden_layers", "dataset", "hash_buckets", "layers"):
        assert not spec.names_a_width(key), key
    for key in ("hidden_size", "head_dim", "q_lora_rank", "moe_intermediate_size",
                "width", "ssm_state_size", "num_experts_per_tok",
                "expansion_factor", "embed_dim"):
        assert spec.names_a_width(key), key


@pytest.mark.parametrize("name", sorted(BREAKS))
def test_benchmark_contract_breach_is_refused(loaded, name):
    with pytest.raises(spec.SpecError):
        spec.validate(_break(loaded, BREAKS[name]))
