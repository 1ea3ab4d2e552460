"""The ``lfm2_8b_a1b`` configuration's file against the published row, its
arithmetic leaf by leaf, its ``work.py`` against figures worked by hand, its
traffic mix, the entries it adds to ``BENCHMARK.json`` and the readers of the
metrics it brings (``benchmark/conv_scopes.py``)."""

import json
import os

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURE_TRACE = os.path.join(REPO, "tests", "benchmark_checks", "fixtures",
                             "tiny_resnet_v5e.xplane.pb.gz")

LAYER_TYPES = (["conv"] * 2 + ["full_attention", "conv", "conv", "conv"] * 4
               + ["full_attention", "conv", "conv"] * 2)
#: ``config`` of the catalog's row for LFM2-8B-A1B (its ``config.json``)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "layer_types": LAYER_TYPES,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
REDUCED = {"num_hidden_layers": 6, "num_dense_layers": 1, "num_experts": 8,
           "vocab_size": 16384}

CELL = "lfm2_8b_a1b_packed_8k"
EXPERT = 3 * 2048 * 1792
CONV = 2048 * 6144 + 3 * 2048 + 2048 * 2048
ATTENTION = 2 * 2048 * 2048 + 2 * 2048 * 512 + 64 + 64
NEW_METRICS = ("conv_mixer_device_ms", "conv_mixer_share_pct",
               "short_conv_roofline_pct", "gqa_attention_device_ms",
               "routed_experts_device_ms", "routed_experts_roofline_pct")


def _config():
    with open(os.path.join(REPO, "benchmark", "configs", "lfm2_8b_a1b",
                           "config.json")) as f:
        return json.load(f)


def _spec():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _spec_entry(group, name):
    return next(e for e in _spec()[group] if e["name"] == name)


def test_benchmark_lfm2_every_width_is_the_published_one():
    config = _config()
    for key, value in PUBLISHED.items():
        assert config[key] == REDUCED.get(key, value), key
        assert type(config[key]) is type(REDUCED.get(key, value)), key
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    entry = _spec_entry("configs", "lfm2_8b_a1b")
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                "num_experts", "vocab_size", "dataset"]
    assert set(config["reduced"]) == set(entry["reduced"])
    assert entry["source"] == config["source"] and len(entry["source"]) < 200
    assert entry["source"].startswith(
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json")
    assert config["experts_held"] == list(range(8))
    assert config["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    # the published layers 1-6: the dense conv layer, then A c c c A
    assert config["layers_run"] == [1, 2, 3, 4, 5, 6]
    assert [LAYER_TYPES[i] for i in config["layers_run"]] == [
        "conv", "full_attention", "conv", "conv", "conv", "full_attention"]
    for choice in ("tie_word_embeddings", "gate_sum_epsilon",
                   "bias_update_speed", "documents", "initialisation",
                   "optimizer", "router_precision", "document_lengths",
                   "recomputation"):
        assert config["assumed"][choice], choice
    assert config["tie_word_embeddings"] is True
    assert config["bias_update_speed"] == 0.001 and config["init_std"] == 0.02
    assert config["optimizer"]["learning_rate"] == 1e-6
    for words in ("each layer shared by 4 chips", "8 experts a chip",
                  "vocabulary-parallel", "first pipeline stage",
                  "no exchange runs on one chip and none is stood in for"):
        assert words in config["deployment"], words


def test_benchmark_lfm2_parameter_count_is_the_issues_arithmetic():
    from benchmark.configs.lfm2_8b_a1b import reference

    config = _config()
    shapes = reference.leaf_shapes(config)
    count = {name: int(np.prod(shape)) for name, (shape, _) in shapes.items()}

    def layer(prefix):
        return sum(n for name, n in count.items() if name.startswith(prefix))

    assert EXPERT == 11_010_048 and 8 * EXPERT == 88_080_384
    assert ATTENTION == (4_194_304 + 1_048_576 + 1_048_576 + 4_194_304
                         + 64 + 64) == 10_485_888
    assert CONV == 12_582_912 + 6_144 + 4_194_304 == 16_783_360
    routed = 8 * EXPERT + 2048 * 32 + 2 * 2048
    assert layer("l00/") == CONV + 3 * 2048 * 7168 + 4_096 == 60_827_648
    for i in (1, 5):
        assert layer(f"l{i:02d}/") == ATTENTION + routed == 98_635_904
    for i in (2, 3, 4):
        assert layer(f"l{i:02d}/") == CONV + routed == 104_933_376
    assert count["embed"] == 16384 * 2048 == 33_554_432
    assert count["final_norm"] == 2_048 and "head" not in count
    assert sum(count.values()) == config["parameters"] == 606_456_064
    assert config["bytes_per_parameter"] * config["parameters"] \
        == 9_703_297_024
    stds = reference.init_stds(config)
    assert stds["normal"] == 0.02
    assert stds["normal_out"] == pytest.approx(0.02 / 48 ** 0.5)
    assert {name.split("/")[-1] for name, (_, kind) in shapes.items()
            if kind == "normal_out"} == {"wo", "out_proj", "mlp_down",
                                         "experts_down"}
    assert {name.split("/")[-1] for name, (_, kind) in shapes.items()
            if kind == "taps"} == {"conv_w"}
    taps = np.asarray(reference.make_leaf(config, 2 ** 31 + 9, "l00/conv_w"))
    assert taps.shape == (3, 2048) and np.abs(taps).max() <= 3 ** -0.5
    assert 0.3 < taps.std() < 0.36           # uniform: bound / sqrt(3)
    # a whole published expert layer, were all 32 experts here: 5.6 GB
    assert CONV + 32 * EXPERT + 2048 * 32 + 4_096 == 369_174_528


def test_benchmark_lfm2_program_builds_the_published_shapes():
    from benchmark.configs.lfm2_8b_a1b import program, reference
    from tensorflowonspark_tpu.models import lfm2_moe

    config = _config()
    model = program.model_config(config)
    assert lfm2_moe.parameter_count(model) == config["parameters"]
    assert model.num_experts == 32 and model.experts_held == tuple(range(8))
    assert model.layer_types == ("conv", "full_attention", "conv", "conv",
                                 "conv", "full_attention")
    assert (model.num_dense_layers, model.head_dim, model.dtype) == (
        1, 64, "bfloat16")
    assert {program.program_name(k): tuple(s) for k, (s, _) in
            reference.leaf_shapes(config).items()} == \
        lfm2_moe.leaf_shapes(model)
    assert lfm2_moe.collection_shapes(model)["bias"] == ((5, 32), "float32")
    for broken in (dict(config, experts_held=[0, 1]),
                   dict(config, layers_run=[1, 2]),
                   dict(config, conv_bias=True)):
        with pytest.raises(ValueError):
            program.model_config(broken)
    from tensorflowonspark_tpu.parallel import moe

    # a quarter share: three times the even share of a row's 32,768 slots
    assert moe.prefix_rows(4 * 8192, 8, 32) == 24_576


def test_benchmark_lfm2_operations_match_the_hand_worked_figures():
    from benchmark.configs.lfm2_8b_a1b import work

    config = _config()
    assert work.mixers(config).count("conv") == 4
    assert work.conv_parameters(config) == 4 * 2048 * 2048 == 16_777_216
    assert work.attention_parameters(config) == 10_485_760
    assert work.expert_parameters(config) == EXPERT
    assert work.expert_layers(config) == 5
    # by hand: four conv mixers, two attention layers, the dense SwiGLU,
    # five routers, the tied head
    by_hand = (4 * 16_777_216 + 2 * 10_485_760 + 3 * 2048 * 7168
               + 5 * 2048 * 32 + 16384 * 2048)
    assert by_hand == 166_002_688
    assert work.matmul_parameters(config) == by_hand
    step = work.step_work(config, 1)
    assert step["flops"] == 6 * by_hand * 8192 == 8_159_364_120_576
    assert step["bytes"] == 2 * 4 * 8192 + 28 * 606_456_064
    assert step["examples"] == 1
    # the routed experts: a row's 32,768 slots, a quarter of them here
    routed = work.experts_work(config, 8192)
    assert routed["flops"] == 6 * EXPERT * 8192 == 541_165_879_296
    assert routed["bytes"] == 3 * 4 * 8 * EXPERT * 5 == 5_284_823_040
    assert work.experts_work(config, 0)["flops"] == 0
    # the short convolutions: B, C, z and the gated result, bfloat16, three
    # passes over four layers; seven operations a channel a pass, the
    # backward pass twice
    conv = work.short_conv_work(config, 8192)
    assert conv["bytes"] == 3 * 4 * 2 * 2048 * 8192 * 4 == 1_610_612_736
    assert conv["flops"] == 4 * 7 * 2048 * 8192 * 4 == 1_879_048_192


def test_benchmark_lfm2_traffic_differs_from_glms_in_the_vocabulary_alone():
    def traffic(name):
        with open(os.path.join(REPO, "benchmark", "traffic",
                               name + ".json")) as f:
            return json.load(f)

    mine = traffic("tfrecord_packed_docs_8k_v16384")
    theirs = traffic("tfrecord_packed_docs_8k_v19360")
    assert "16,384" in mine.pop("note") and theirs.pop("note")
    assert mine.pop("vocab") == 16384 and theirs.pop("vocab") == 19360
    assert mine == theirs
    assert (mine["warmup_steps"], mine["trace_after_steps"],
            mine["trace_steps"], mine["batch_per_chip"]) == (3, 8, 5, 1)
    cell = _spec_entry("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2_8b_a1b", "tfrecord_packed_docs_8k_v16384", 1)
    assert "1/4" in cell["why"] and len(cell["why"]) <= 200
    from benchmark.traffic import packed_documents

    full = traffic("tfrecord_packed_docs_8k_v16384")
    rows = packed_documents.rows(full, 2 ** 31 + 7, [0, 1023])
    assert rows["tokens"].shape == (2, 8192)
    assert 0 <= rows["tokens"].min() and rows["tokens"].max() < 16384


def test_benchmark_lfm2_entries_pass_the_contracts_static_rules():
    """``spec.validate`` and ``validate_files`` with the new entries; the
    accepted benchmark's entries lead theirs, unchanged in number."""
    from benchmark import spec

    spec_ = spec.load(REPO)
    spec.validate(spec_)
    spec.validate_files(spec_)
    assert [c["name"] for c in spec_["configs"]][-1] == "lfm2_8b_a1b"
    assert [w["name"] for w in spec_["workloads"]][-1] == CELL
    assert tuple(m["name"] for m in spec_["per_layer"][-6:]) == NEW_METRICS
    mine = {m["name"] for m in spec.metrics_of(spec_, CELL, "per_layer")}
    assert set(NEW_METRICS) <= mine
    assert {"step_device_ms", "step_roofline_pct", "feed_wait_ms"} <= mine
    assert not mine & {"mla_device_ms", "ssm_scan_device_ms",
                       "moe_experts_device_ms", "attention_device_ms"}
    cell = spec.cell(spec_, CELL)
    assert cell["config_package"] == "benchmark.configs.lfm2_8b_a1b"
    with open(os.path.join(REPO, "benchmark", "configs", "lfm2_8b_a1b",
                           "limits.json")) as f:
        limits = json.load(f)
    assert set(limits["limits"]) == {
        "loss_step1_rel", "loss_step2_rel", "loss_step3_rel",
        "first_grad_norm_gap", "param_change_norm_gap"}
    assert "control" in limits["readings"].lower()


def _run(moe_scope_s=None, conv_scope_s=None, counters=None, steps=5):
    run = {"cell": {"name": CELL, "chips": 1,
                    "config_package": "benchmark.configs.lfm2_8b_a1b",
                    "config_values": _config(),
                    "traffic_values": {"batch_per_chip": 1}},
           "trainer": {"trace": {"busy_s": 2.0, "steps": steps}},
           "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
           "notes": [], "_moe_scopes": None, "_conv_scopes": None,
           "_program": {"spans": {}, "dropped": 0, "counters": counters}}
    for key, scope_s in (("_moe_scopes", moe_scope_s),
                         ("_conv_scopes", conv_scope_s)):
        if scope_s is not None:
            run[key] = {"steps": steps, "scope_s": scope_s, "top_ops": []}
    return run


@pytest.mark.parametrize("name", NEW_METRICS)
def test_benchmark_lfm2_metric_is_left_out_where_there_is_nothing_to_read(
        name):
    """An untraced run, and a program without the scopes or the counters
    (the parent of the PR that brought them): None, never a raise."""
    from benchmark import conv_scopes, moe_scopes, spec

    reader = spec.module("benchmark", "metrics", name)
    assert reader.read(_run()) is None
    assert reader.read(_run({s: 0.0 for s in moe_scopes.SCOPES},
                            {s: 0.0 for s in conv_scopes.SCOPES},
                            {"n:1": {"counters": {}}})) is None
    entry = _spec_entry("per_layer", name)
    assert entry["workloads"] == [CELL]
    assert (entry["moves"], entry["source"], entry["layer"]) == (
        "examples_per_s_chip", "device_trace", "kernels")
    assert entry["unit"] == ("%" if name.endswith("_pct") else "ms")


def test_benchmark_lfm2_metrics_read_the_scopes_and_the_counters():
    from benchmark.metrics import (conv_mixer_device_ms, conv_mixer_share_pct,
                                   gqa_attention_device_ms,
                                   routed_experts_device_ms,
                                   routed_experts_roofline_pct,
                                   short_conv_roofline_pct)

    moe_s = {"attention": 0.3, "mlp": 0.1, "moe_router": 0.01,
             "moe_dispatch": 0.02, "moe_combine": 0.03, "moe_experts": 0.02,
             "ragged-dot": 0.13, "lm_head": 0.05}
    conv_s = {"conv_mixer": 0.25, "conv_in_proj": 0.1, "short_conv": 0.05,
              "conv_out_proj": 0.05, "qk_norm_rope": 0.02}
    counters = {"node:1": {"counters": {"moe_local_slots_total": 10 * 40960,
                                        "trainer_steps_total": 10}},
                "driver:2": {"counters": {}}}
    run = _run(moe_s, conv_s, counters)
    assert conv_mixer_device_ms.read(run) == pytest.approx(50.0)
    assert conv_mixer_share_pct.read(run) == pytest.approx(100 * 50 / 400)
    assert gqa_attention_device_ms.read(run) == pytest.approx(60.0)
    assert routed_experts_device_ms.read(run) == pytest.approx(30.0)
    # 1.61 GB at 819 GB/s is 1.967 ms of the scope's 10: memory bound
    share = short_conv_roofline_pct.read(run)
    assert share == pytest.approx(100 * (1_610_612_736 / 819e9) / 10e-3)
    assert 19 < share < 20
    assert any(n.startswith("short_conv_roofline_pct: memory bound")
               for n in run["notes"])
    # 40,960 local slots a step: 6 x 3 x 2048 x 1792 operations each (13.7
    # ms at the peak) against 5.28 GB of weights (6.45 ms): compute
    share = routed_experts_roofline_pct.read(run)
    assert share == pytest.approx(
        100 * (6 * EXPERT * 40960 / 197e12) / 30e-3)
    assert 45 < share < 46
    assert any(n.startswith("moe_experts_roofline_pct: compute bound")
               for n in run["notes"])   # one reader behind both names
    # an even router's 8,192 slots: the weights' bytes bound it
    counters["node:1"]["counters"]["moe_local_slots_total"] = 10 * 8192
    run = _run(moe_s, conv_s, counters)
    assert routed_experts_roofline_pct.read(run) == pytest.approx(
        100 * (5_284_823_040 / 819e9) / 30e-3)


def test_benchmark_lfm2_conv_scopes_read_a_trace_of_a_program_without_them():
    """``conv_scopes.reduced`` through its child process on a recorded v5e
    trace of the tiny ResNet step: every scope reads zero seconds, the
    readers return nothing, nothing raises — what a parent that lacks the
    model leaves this PR's readers with."""
    from benchmark import conv_scopes
    from benchmark.metrics import conv_mixer_device_ms, short_conv_roofline_pct

    run = _run()
    del run["_conv_scopes"]
    run["trainer"]["trace"]["file"] = FIXTURE_TRACE
    out = conv_scopes.reduced(run)
    assert out["steps"] > 0
    assert out["scope_s"] == {s: 0.0 for s in conv_scopes.SCOPES}
    assert conv_scopes.reduced(run) is out          # read once
    assert conv_mixer_device_ms.read(run) is None
    assert short_conv_roofline_pct.read(run) is None
    assert any("mixers' scopes" in n for n in run["notes"])
    gone = _run()
    del gone["_conv_scopes"]
    gone["trainer"]["trace"]["file"] = FIXTURE_TRACE + ".absent"
    assert conv_scopes.reduced(gone) is None
