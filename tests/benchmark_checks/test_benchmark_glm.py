"""The ``glm_4_7_flash`` configuration's file against the published row, its
``work.py`` against figures worked by hand, its traffic mix, and the readers
of the metrics it brings (``benchmark/moe_scopes.py``)."""

import json
import os

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: ``config`` of the catalog's row for GLM-4.7-Flash (its ``config.json``)
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}
REDUCED = {"num_hidden_layers": 5, "n_routed_experts": 8, "vocab_size": 19360}

MLA = 2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048
EXPERT = 3 * 2048 * 1536


def _config():
    with open(os.path.join(REPO, "benchmark", "configs", "glm_4_7_flash",
                           "config.json")) as f:
        return json.load(f)


def _spec_entry(group, name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return next(e for e in json.load(f)[group] if e["name"] == name)


def test_benchmark_glm_every_width_is_the_published_one():
    config = _config()
    for key, value in PUBLISHED.items():
        assert config[key] == REDUCED.get(key, value), key
        assert type(config[key]) is type(REDUCED.get(key, value)), key
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    entry = _spec_entry("configs", "glm_4_7_flash")
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size", "dataset"]
    assert set(config["reduced"]) == set(entry["reduced"])
    assert entry["source"] == config["source"] and len(entry["source"]) < 200
    assert config["experts_held"] == list(range(8))
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    for choice in ("mtp_loss_weight", "bias_update_speed", "rope",
                   "mtp_module", "initialisation", "optimizer",
                   "recomputation"):
        assert config["assumed"][choice], choice
    assert config["mtp_loss_weight"] == 0.3 and config["init_std"] == 0.02
    assert config["bias_update_speed"] == 0.001
    for words in ("each layer shared by 8 chips", "8 experts a chip",
                  "vocabulary-parallel", "MTP module",
                  "no exchange runs on one chip and none is stood in for"):
        assert words in config["deployment"], words


def test_benchmark_glm_parameter_count_is_the_issues_arithmetic():
    from benchmark.configs.glm_4_7_flash import reference

    config = _config()
    shapes = reference.leaf_shapes(config)
    count = {name: int(np.prod(shape)) for name, (shape, _) in shapes.items()}

    def layer(prefix):
        return sum(n for name, n in count.items() if name.startswith(prefix))

    assert MLA + 768 + 512 == 21_759_232
    assert EXPERT == 9_437_184
    assert layer("l00/") == 21_759_232 + 2 * 2048 + 3 * 2048 * 10240 \
        == 84_677_888
    for i in range(1, 5):
        assert layer(f"l{i:02d}/") == (21_759_232 + 2 * 2048 + 2048 * 64
                                       + 9 * EXPERT) == 106_829_056
    assert layer("mtp/") == 106_829_056 + 3 * 2048 + 4096 * 2048 \
        == 115_223_808
    assert count["embed"] + count["head"] + count["final_norm"] \
        == 2 * 19360 * 2048 + 2048 == 79_300_608
    assert sum(count.values()) == config["parameters"] == 706_518_528
    stds = reference.init_stds(config)
    assert stds["normal"] == 0.02
    assert stds["normal_out"] == pytest.approx(0.02 / 94 ** 0.5)
    assert {name.split("/")[-1] for name, (_, kind) in shapes.items()
            if kind == "normal_out"} == {"wo", "mlp_down", "shared_down",
                                         "experts_down"}
    assert config["bytes_per_parameter"] * config["parameters"] \
        == 11_304_296_448
    # the whole layer, were all 64 experts here: a chip cannot hold two
    assert 106_829_056 + 56 * EXPERT == 635_311_360


def test_benchmark_glm_program_builds_the_published_shapes():
    from benchmark.configs.glm_4_7_flash import program, reference
    from tensorflowonspark_tpu.models import mla_moe

    config = _config()
    model = program.model_config(config)
    assert mla_moe.parameter_count(model) == config["parameters"]
    assert model.n_routed_experts == 64 and model.experts_held == tuple(
        range(8))
    assert {program.program_name(k): tuple(s) for k, (s, _) in
            reference.leaf_shapes(config).items()} == \
        mla_moe.leaf_shapes(model)
    assert mla_moe.collection_shapes(model)["bias"] == ((5, 64), "float32")
    with pytest.raises(ValueError):
        program.model_config(dict(config, experts_held=[0, 1]))


def test_benchmark_glm_operations_match_the_hand_worked_figures():
    from benchmark.configs.glm_4_7_flash import work

    config = _config()
    assert work.attention_parameters(config) == MLA == 21_757_952
    assert work.expert_parameters(config) == EXPERT
    # by hand: six layers' attention, the dense feed-forward, five shared
    # experts and routers, eh_proj, the head for both losses
    by_hand = (6 * 21_757_952 + 3 * 2048 * 10240 + 5 * (EXPERT + 2048 * 64)
               + 2 * 2048 * 2048 + 2 * 19360 * 2048)
    assert by_hand == 328_990_720
    assert work.matmul_parameters(config) == by_hand
    step = work.step_work(config, 1)
    assert step["flops"] == 6 * by_hand * 8192 == 16_170_551_869_440
    assert step["bytes"] == 2 * 4 * 8192 + 28 * 706_518_528
    assert step["examples"] == 1
    # the routed experts: a row's 32,768 slots, an eighth of them here
    per_layer = work.experts_work(config, 4096)
    assert per_layer["flops"] == 6 * EXPERT * 4096 == 231_928_233_984
    assert per_layer["bytes"] == 3 * 4 * 8 * EXPERT * 5 == 4_529_848_320
    assert work.experts_work(config, 0)["flops"] == 0


def test_benchmark_glm_traffic_is_the_issues():
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "tfrecord_packed_docs_8k_v19360.json")) as f:
        traffic = json.load(f)
    traffic.pop("note")
    assert traffic == {
        "generator": "packed_documents", "feed": "tfrecord_readers",
        "records": 1024, "shards": 8, "seq_len": 8192, "vocab": 19360,
        "zipf_s": 1.0, "doc_median": 600, "doc_sigma": 1.2, "doc_min": 16,
        "doc_max": 8192, "batch_per_chip": 1, "readers": 1,
        "shuffle_buffer": 0, "prefetch": 2, "warmup_steps": 3,
        "trace_after_steps": 8, "trace_steps": 5, "max_epochs": 64}
    cell = _spec_entry("workloads", "glm47_flash_packed_8k")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm_4_7_flash", "tfrecord_packed_docs_8k_v19360", 1)
    from benchmark.traffic import packed_documents

    rows = packed_documents.rows(traffic, 2 ** 31 + 7, [0, 1023])
    assert rows["tokens"].shape == (2, 8192)
    assert 0 <= rows["tokens"].min() and rows["tokens"].max() < 19360


def _run(scope_s=None, counters=None, steps=5):
    run = {"cell": {"name": "glm47_flash_packed_8k", "chips": 1,
                    "config_package": "benchmark.configs.glm_4_7_flash",
                    "config_values": _config(),
                    "traffic_values": {"batch_per_chip": 1}},
           "trainer": {"trace": {"busy_s": 2.0, "steps": steps}},
           "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
           "notes": [], "_moe_scopes": None,
           "_program": {"spans": {}, "dropped": 0, "counters": counters}}
    if scope_s is not None:
        run["_moe_scopes"] = {"steps": steps, "scope_s": scope_s,
                              "top_ops": []}
    return run


NEW_METRICS = ("moe_experts_device_ms", "moe_experts_roofline_pct",
               "moe_route_device_ms", "mla_device_ms", "mtp_share_pct")


@pytest.mark.parametrize("name", NEW_METRICS)
def test_benchmark_glm_metric_is_left_out_where_there_is_nothing_to_read(name):
    """An untraced run, and a program without the scopes or the counters
    (the parent of the PR that brought them): None, never a raise."""
    from benchmark import spec

    reader = spec.module("benchmark", "metrics", name)
    assert reader.read(_run()) is None
    empty = {s: 0.0 for s in ("attention", "moe_experts", "ragged-dot", "mtp",
                              "moe_router", "moe_dispatch", "moe_combine")}
    assert reader.read(_run(empty, {"n:1": {"counters": {}}})) is None
    entry = _spec_entry("per_layer", name)
    assert entry["workloads"] == ["glm47_flash_packed_8k"]
    assert entry["moves"] == "examples_per_s_chip"


def test_benchmark_glm_metrics_read_the_scopes_and_the_counters():
    from benchmark import moe_scopes
    from benchmark.metrics import (mla_device_ms, moe_experts_device_ms,
                                   moe_experts_roofline_pct,
                                   moe_route_device_ms, mtp_share_pct)

    scope_s = {"attention": 1.0, "mla_project": 0.2, "mtp": 0.25,
               "moe_router": 0.01, "moe_dispatch": 0.02, "moe_combine": 0.03,
               "moe_experts": 0.01, "ragged-dot": 0.04, "mlp": 0.1}
    counters = {"node:1": {"counters": {"moe_local_slots_total": 10 * 20480,
                                        "trainer_steps_total": 10}},
                "driver:2": {"counters": {}}}
    run = _run(scope_s, counters)
    assert moe_scopes.GROUPED_PRODUCT in moe_scopes.SCOPES
    assert mla_device_ms.read(run) == pytest.approx(200.0)
    assert moe_route_device_ms.read(run) == pytest.approx(12.0)
    assert moe_experts_device_ms.read(run) == pytest.approx(10.0)
    assert mtp_share_pct.read(run) == pytest.approx(100 * 50.0 / 400.0)
    # 20,480 local slots a step: 6 x 3 x 2048 x 1536 operations each
    # (5.89 ms at the peak) against 4.53 GB of weights (5.53 ms): compute
    share = moe_experts_roofline_pct.read(run)
    assert share == pytest.approx(
        100 * (6 * EXPERT * 20480 / 197e12) / 10e-3)
    assert 58 < share < 60
    assert any("compute bound" in note for note in run["notes"])
    # few slots: the weights' bytes bound it
    counters["node:1"]["counters"]["moe_local_slots_total"] = 10 * 1000
    run = _run(scope_s, counters)
    assert moe_experts_roofline_pct.read(run) == pytest.approx(
        100 * (4_529_848_320 / 819e9) / 10e-3)
