"""The ``trinity_mini`` configuration's file against the published row, its
arithmetic leaf by leaf, its ``work.py`` against figures worked by hand, its
traffic mix, the entries it adds to ``BENCHMARK.json`` and the readers of the
metrics it brings (``benchmark/afmoe_scopes.py``).

What is pinned of ``BENCHMARK.json`` is pinned from the front (prefixes, an
entry's own first cell), never "is the last" or "is the whole list": a later
cell's appends must not fail a test of this one.
"""

import json
import os

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURE_TRACE = os.path.join(REPO, "tests", "benchmark_checks", "fixtures",
                             "tiny_resnet_v5e.xplane.pb.gz")

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SLIDING, FULL = "sliding_attention", "full_attention"
#: ``config`` of the catalog's row for Trinity-Mini (its ``config.json``)
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 8,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
REDUCED = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 16,
           "vocab_size": 25024}

CONFIG = "trinity_mini"
CELL = "trinity_mini_packed_8k"
TRAFFIC = "tfrecord_packed_docs_8k_v25024"
EXPERT = 3 * 2048 * 1024
DENSE = 3 * 2048 * 6144
ATTENTION = 3 * 2048 * 32 * 128 + 2 * 2048 * 4 * 128
NEW_METRICS = ("gated_attention_device_ms", "gated_attention_share_pct",
               "attention_gate_device_ms", "post_norm_device_ms",
               "swa2048_blocks_device_ms", "swa2048_blocks_roofline_pct",
               "nope_full_blocks_device_ms", "moe128_experts_device_ms",
               "moe128_experts_roofline_pct", "moe128_route_device_ms")
ALL_CELL_METRICS = ("device_idle_pct", "idle_feed_pct", "idle_h2d_pct",
                    "idle_host_pct", "h2d_wait_ms", "device_step_est_ms",
                    "device_step_est_err_pct", "step_enqueue_pct",
                    "h2d_transfer_ms")


def _config():
    with open(os.path.join(REPO, "benchmark", "configs", CONFIG,
                           "config.json")) as f:
        return json.load(f)


def _spec():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _spec_entry(group, name):
    return next(e for e in _spec()[group] if e["name"] == name)


def test_benchmark_trinity_every_width_is_the_published_one():
    config = _config()
    for key, value in PUBLISHED.items():
        assert config[key] == REDUCED.get(key, value), key
        assert type(config[key]) is type(REDUCED.get(key, value)), key
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    if os.path.isfile(CATALOG):     # where the catalog is at hand
        with open(CATALOG) as f:
            row, = [r for r in map(json.loads, f)
                    if r["name"] == "Trinity-Mini"]
        assert row["config"] == PUBLISHED
        assert config["source"].startswith(row["source_url"])
    entry = _spec_entry("configs", CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                "num_experts", "vocab_size", "dataset"]
    assert set(config["reduced"]) == set(entry["reduced"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["source"].startswith(
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json")
    # the cut: the published layers 1-5, no layer changes kind, the leading
    # dense layers counted once; experts 0-15 of 128; an eighth of the ids
    assert config["layers_run"] == [1, 2, 3, 4, 5]
    assert len(config["layers_run"]) == config["num_hidden_layers"]
    assert [config["layer_types"][i] for i in config["layers_run"]] == [
        SLIDING, SLIDING, FULL, SLIDING, SLIDING]
    assert sum(i < PUBLISHED["num_dense_layers"]
               for i in config["layers_run"]) == config["num_dense_layers"]
    assert config["experts_held"] == list(range(16))
    assert len(config["experts_held"]) == config["num_experts"] \
        == PUBLISHED["num_experts"] // 8
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    for choice in ("attention_gate", "qk_norm", "rope", "norms",
                   "mup_enabled", "router", "correction_bias",
                   "auxiliary_loss", "window", "documents", "initialisation",
                   "optimizer", "router_precision", "document_lengths",
                   "recomputation"):
        assert config["assumed"][choice], choice
    assert "i - j < 2048" in config["assumed"]["window"]
    assert "1e-20" in config["assumed"]["router"]
    assert config["gate_sum_eps"] == 1e-20
    assert config["init_std"] == 0.02 and config["dtype"] == "bfloat16"
    assert config["param_dtype"] == "float32"
    assert config["optimizer"]["learning_rate"] == 1e-6
    assert config["control_precision"] == "float8"
    assert (config["program_model"], config["seq_len"],
            config["batch_per_chip"]) == ("afmoe", 8192, 1)
    for words in ("each layer shared by 8 chips", "16 experts a chip",
                  "the shared expert computed by every chip alike",
                  "vocabulary-parallel over the same 8",
                  "first pipeline stage",
                  "no exchange runs on one chip and none is stood in for"):
        assert words in config["deployment"], words


def test_benchmark_trinity_parameter_count_is_the_issues_arithmetic():
    from benchmark.configs.trinity_mini import reference

    config = _config()
    shapes = reference.leaf_shapes(config)
    count = {name: int(np.prod(shape)) for name, (shape, _) in shapes.items()}

    def layer(prefix):
        return sum(n for name, n in count.items() if name.startswith(prefix))

    assert EXPERT == 6_291_456 and 16 * EXPERT == 100_663_296
    assert DENSE == 37_748_736
    assert ATTENTION == 3 * 8_388_608 + 2 * 1_048_576 == 27_262_976
    for i in range(5):
        pre = f"l{i:02d}/"
        assert count[pre + "wq"] == count[pre + "wg"] == count[pre + "wo"] \
            == 8_388_608
        assert count[pre + "wk"] == count[pre + "wv"] == 1_048_576
        assert count[pre + "q_norm"] == count[pre + "k_norm"] == 128
        assert [count[pre + f"norm{n}"] for n in (1, 2, 3, 4)] == [2_048] * 4
    assert {k.split("/")[1] for k in count if k.startswith("l00/mlp")} == {
        "mlp_gate", "mlp_up", "mlp_down"}
    assert layer("l00/") == ATTENTION + 256 + 4 * 2_048 + DENSE \
        == 65_020_160
    for i in range(1, 5):
        pre = f"l{i:02d}/"
        assert count[pre + "router"] == 2048 * 128 == 262_144
        for leaf in ("shared_gate", "shared_up", "shared_down"):
            assert count[pre + leaf] == 2048 * 1024
        for leaf in ("experts_gate", "experts_up", "experts_down"):
            assert count[pre + leaf] == 16 * 2048 * 1024 == 33_554_432
        assert layer(pre) == ATTENTION + 256 + 4 * 2_048 + 262_144 \
            + 17 * EXPERT == 134_488_320
    assert count["embed"] == count["head"] == 25024 * 2048 == 51_249_152
    assert count["final_norm"] == 2_048
    assert sum(count.values()) == config["parameters"] == 705_473_792 \
        == 65_020_160 + 4 * 134_488_320 + 2 * 51_249_152 + 2_048
    assert config["bytes_per_parameter"] * config["parameters"] \
        == 11_287_580_672
    # the whole published model by the same equations: the published 26B,
    # and an expert layer no chip can hold
    whole_layer = ATTENTION + 256 + 4 * 2_048 + 262_144 + 129 * EXPERT
    assert whole_layer == 839_131_392 and 16 * whole_layer > 13.4e9
    assert (2 * 65_020_160 + 30 * whole_layer + 2 * 200192 * 2048 + 2048
            == 26_123_970_560)
    stds = reference.init_stds(config)
    assert stds["normal"] == 0.02 and "embed_init_std" not in config
    assert stds["normal_out"] == pytest.approx(0.02 / 64 ** 0.5)
    kinds = {}
    for name, (_, kind) in shapes.items():
        kinds.setdefault(kind, set()).add(name.split("/")[-1])
    assert kinds["normal_out"] == {"wo", "mlp_down", "shared_down",
                                   "experts_down"}
    assert {"embed", "head", "wq", "wg", "router"} <= kinds["normal"]
    assert kinds["ones"] == {"norm1", "norm3", "q_norm", "k_norm",
                             "final_norm"}
    assert kinds["post_scale"] == {"norm2", "norm4"}
    assert stds["post_scale"] == config["post_norm_init"]
    scale = np.asarray(reference.make_leaf(config, 7, "l02/norm4"))
    assert scale.shape == (2048,) and np.all(
        scale == np.float32(config["post_norm_init"]))
    seed = 2 ** 31 + 9
    wk = np.asarray(reference.make_leaf(config, seed, "l03/wk"))
    assert wk.shape == (2048, 512) and 0.0195 < wk.std() < 0.0205
    again = np.asarray(reference.make_leaf(config, seed, "l03/wk"))
    np.testing.assert_array_equal(wk, again)    # a leaf at a time, any time


def test_benchmark_trinity_program_builds_the_published_shapes():
    from benchmark.configs.trinity_mini import program, reference
    from tensorflowonspark_tpu.models import afmoe
    from tensorflowonspark_tpu.parallel import moe

    config = _config()
    model = program.model_config(config)
    assert afmoe.parameter_count(model) == config["parameters"]
    assert model.num_experts == 128 and model.experts_held == tuple(range(16))
    assert afmoe.layer_kinds(model) == [
        ("l00_", SLIDING, "dense"), ("l01_", SLIDING, "experts"),
        ("l02_", FULL, "experts"), ("l03_", SLIDING, "experts"),
        ("l04_", SLIDING, "experts")]
    assert (model.num_attention_heads, model.num_key_value_heads,
            model.head_dim, model.sliding_window, model.rope_theta,
            model.dtype, model.mup_enabled) == (
        32, 4, 128, 2048, 10000, "bfloat16", True)
    assert {program.program_name(k): tuple(s) for k, (s, _) in
            reference.leaf_shapes(config).items()} == \
        afmoe.leaf_shapes(model)
    assert afmoe.collection_shapes(model)["bias"] == ((4, 128), "float32")
    assert reference.zero_bias(config).shape == (4, 128)
    assert reference.bias_rows(config) == [None, 0, 1, 2, 3]
    routing = afmoe.routing(model)
    assert (routing.score, routing.top_k, routing.speed, routing.scale,
            routing.sum_eps, routing.normalize) == (
                "sigmoid", 8, 0.001, 2.826, 1e-20, True)
    for broken in (dict(config, experts_held=[0, 1]),
                   dict(config, layers_run=[1, 2]),
                   dict(config, num_dense_layers=2),
                   dict(config, tie_word_embeddings=True),
                   dict(config, rope_scaling={"type": "yarn"}),
                   dict(config, n_group=2),
                   dict(config, gate_sum_eps=1e-6)):
        with pytest.raises(ValueError):
            program.model_config(broken)
    # an eighth share of a row's 65,536 slots: 8,192 even, the three sizes
    assert moe.row_sizes(8 * 8192, 16, 128) == (
        moe.tight_rows(8 * 8192, 16, 128), 24_576, 65_536)
    assert 8_192 < moe.tight_rows(8 * 8192, 16, 128) <= 12_288


def test_benchmark_trinity_operations_match_the_hand_worked_figures():
    from benchmark.configs.trinity_mini import work

    config = _config()
    assert work.mixers(config) == [SLIDING, SLIDING, FULL, SLIDING, SLIDING]
    assert work.attention_parameters(config) == ATTENTION
    assert work.expert_parameters(config) == EXPERT
    assert work.expert_layers(config) == 4
    # by hand: five layers' five projections, the dense feed-forward, four
    # layers' routers and shared experts, the untied head
    by_hand = (5 * ATTENTION + DENSE + 4 * (2048 * 128 + EXPERT)
               + 25024 * 2048)
    assert by_hand == 251_527_168
    assert work.matmul_parameters(config) == by_hand
    step = work.step_work(config, 1)
    assert step["flops"] == 6 * by_hand * 8192 == 12_363_063_361_536
    assert step["bytes"] == 2 * 4 * 8192 + 28 * 705_473_792
    assert step["examples"] == 1
    # the routed experts: a row's 65,536 slots, an eighth of them here
    routed = work.experts_work(config, 8192)
    assert routed["flops"] == 6 * EXPERT * 8192 == 309_237_645_312
    assert routed["bytes"] == 3 * 4 * 16 * EXPERT * 4 == 4_831_838_208
    assert work.experts_work(config, 0)["flops"] == 0
    # the triangle of a row of 8,192 and the band of 2,048 under it, by
    # hand: a query at place i holds min(i + 1, 2,048) keys
    triangle = 8192 * 8193 // 2
    band = sum(min(i + 1, 2048) for i in range(8192))
    assert (triangle, band) == (33_558_528, 14_681_088)
    assert band / triangle == pytest.approx(0.4375, abs=2e-4)
    assert work.mask_pairs(8192) == triangle
    assert work.mask_pairs(8192, 2048) == band \
        == 8192 * 2048 - 2048 * 2047 // 2
    assert work.mask_pairs(600, 2048) == 600 * 601 // 2
    # forward, a layer: two products of 2 operations a pair a number of a
    # head's 128, 32 heads; a step makes them 3.5 times (the forward blocks
    # once — a recomputed layer keeps what they made — and the backward
    # pass's five products), not the 4.5 of a second forward pass
    full = work.full_attention_work(config, 8192)
    assert full["flops"] == int(3.5 * 4 * triangle * 128 * 32) \
        == 1_924_380_229_632
    window = work.window_attention_work(config, 8192)
    assert window["flops"] == 4 * int(3.5 * 4 * band * 128 * 32) \
        == 4 * 841_872_310_272
    # q and o of 32 heads, k and v of 4, bfloat16: 18,432 B a token, three
    # passes a step (forward; backward with the gradients beside them)
    assert full["bytes"] == 3 * 2 * (32 + 4) * 128 * 2 * 8192 == 452_984_832
    assert window["bytes"] == 4 * full["bytes"]
    assert work.window_attention_work(config, 16384)["flops"] \
        == 2 * window["flops"]
    # the gate: one 2,048 x 4,096 product a layer; its weights' 33.5 MB
    # three times and 20,480 B a token twice
    gate = work.attention_gate_work(config, 8192)
    assert gate["flops"] == 6 * 2048 * 4096 * 8192 * 5 == 2_061_584_302_080
    assert gate["bytes"] == 5 * (12 * 2048 * 4096
                                 + 2 * (2048 + 2 * 4096) * 2 * 8192)
    norms = work.post_norm_work(config, 8192)
    assert norms["bytes"] == 10 * 2 * 3 * 2048 * 2 * 8192 == 2_013_265_920
    assert norms["flops"] == 10 * 2 * 4 * 2048 * 8192


def test_benchmark_trinity_traffic_differs_from_mellums_in_the_vocabulary():
    def traffic(name):
        with open(os.path.join(REPO, "benchmark", "traffic",
                               name + ".json")) as f:
            return json.load(f)

    mine = traffic(TRAFFIC)
    theirs = traffic("tfrecord_packed_docs_8k_v24576")
    assert "25,024" in mine.pop("note") and theirs.pop("note")
    assert mine.pop("vocab") == 25024 and theirs.pop("vocab") == 24576
    assert mine == theirs
    assert (mine["warmup_steps"], mine["trace_after_steps"],
            mine["trace_steps"], mine["batch_per_chip"]) == (3, 8, 5, 1)
    cell = _spec_entry("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert "1/8" in cell["why"] and len(cell["why"]) <= 200
    from benchmark.traffic import packed_documents

    rows = packed_documents.rows(traffic(TRAFFIC), 2 ** 31 + 7, [0, 1023])
    assert rows["tokens"].shape == (2, 8192)
    assert 0 <= rows["tokens"].min() and rows["tokens"].max() < 25024
    # documents shorter and longer than the window in the mix's rows
    lengths = np.concatenate([np.diff(np.flatnonzero(np.r_[
        True, seg[1:] != seg[:-1], True])) for seg in rows["segment_ids"]])
    assert lengths.min() < 2048 < lengths.max()


def test_benchmark_trinity_entries_follow_the_accepted_ones_in_their_order():
    """``spec.validate`` and ``validate_files`` with the new entries; the
    accepted benchmark's entries lead, in the accepted order, and this
    cell's follow them: counted from the front, so that a later cell's
    entries move nothing here."""
    from benchmark import spec

    spec_ = spec.load(REPO)
    spec.validate(spec_)
    spec.validate_files(spec_)
    assert [c["name"] for c in spec_["configs"]][:8] == [
        "resnet50", "criteo_widedeep", "granite_4_0_h_micro",
        "glm_4_7_flash", "lfm2_8b_a1b", "kimi_linear_48b_a3b",
        "mellum2_12b_a2_5b", CONFIG]
    cells = [w["name"] for w in spec_["workloads"]]
    assert cells[:8] == [
        "resnet50_fed", "widedeep_spark_fed", "granite_h_micro_packed_8k",
        "glm47_flash_packed_8k", "lfm2_8b_a1b_packed_8k",
        "kimi_linear_packed_8k", "mellum2_packed_8k", CELL]
    names = [m["name"] for m in spec_["per_layer"]]
    at = names.index("attention_mixer_share_pct")   # the last before these
    assert tuple(names[at + 1:at + 11]) == NEW_METRICS
    for name in ALL_CELL_METRICS:
        assert _spec_entry("per_layer", name)["workloads"][:8] == cells[:8]
    assert _spec_entry("per_layer", "loss_tokens_per_s_chip")[
        "workloads"][:4] == ["granite_h_micro_packed_8k",
                             "kimi_linear_packed_8k", "mellum2_packed_8k",
                             CELL]
    for name in NEW_METRICS:
        entry = _spec_entry("per_layer", name)
        assert entry["workloads"][0] == CELL
        assert (entry["moves"], entry["source"], entry["layer"]) == (
            "examples_per_s_chip", "device_trace", "kernels")
        assert entry["unit"] == ("%" if name.endswith("_pct") else "ms")
        assert entry["better"] == ("higher" if "roofline" in name
                                   else "lower")
    for e in spec_["configs"] + spec_["workloads"]:
        assert len(e["why"]) <= 200 and "\n" not in e["why"]
    assert not any(spec.names_a_width(k) for c in spec_["configs"]
                   for k in c["reduced"])
    assert sum(w["chips"] == 4 for w in spec_["workloads"]) == 0


def test_benchmark_trinity_cell_reports_its_own_metrics_and_has_its_limits():
    from benchmark import spec

    spec_ = spec.load(REPO)
    mine = {m["name"] for m in spec.metrics_of(spec_, CELL, "per_layer")}
    assert set(NEW_METRICS + ALL_CELL_METRICS
               + ("loss_tokens_per_s_chip",)) <= mine
    assert {"step_device_ms", "step_roofline_pct", "feed_wait_ms"} <= mine
    assert not mine & {
        "window_attention_device_ms", "full_attention_device_ms",
        "window_attention_roofline_pct", "attention_mixer_share_pct",
        "mla_device_ms", "ssm_scan_device_ms", "moe_experts_device_ms",
        "attention_device_ms", "gqa_attention_device_ms",
        "routed_experts_device_ms", "kda_scan_device_ms"}
    assert {m["name"] for m in spec.metrics_of(spec_, CELL, "end_to_end")} \
        == {"setup_s", "examples_per_s_chip", "step_ms_p95"}
    assert spec.cell(spec_, CELL)["config_package"] == (
        "benchmark.configs." + CONFIG)
    with open(os.path.join(REPO, "benchmark", "configs", CONFIG,
                           "limits.json")) as f:
        limits = json.load(f)
    assert set(limits["limits"]) == {
        "loss_step1_rel", "loss_step2_rel", "loss_step3_rel",
        "first_grad_norm_gap", "param_change_norm_gap"}
    readings = limits["readings"].lower()
    for word in ("control", "gate", "post-norm", "rotated", "window"):
        assert word in readings, word


def _run(scope_s=None, steps=5, cell=CELL, config=CONFIG, counters=None):
    with open(os.path.join(REPO, "benchmark", "configs", config,
                           "config.json")) as f:
        values = json.load(f)
    run = {"cell": {"name": cell, "chips": 1,
                    "config_package": "benchmark.configs." + config,
                    "config_values": values,
                    "traffic_values": {"batch_per_chip": 1}},
           "trainer": {"trace": {"busy_s": 2.0, "steps": steps}},
           "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
           "notes": [], "_afmoe_scopes": None,
           "_program": {"spans": {}, "dropped": 0, "counters": counters}}
    if scope_s is not None:
        run["_afmoe_scopes"] = {"steps": steps, "scope_s": scope_s,
                                "top_ops": []}
    return run


@pytest.mark.parametrize("name", NEW_METRICS)
def test_benchmark_trinity_metric_is_left_out_where_there_is_nothing_to_read(
        name):
    """An untraced run, and a program without the scopes or the counters
    (the parent of the PR that brought them): None, never a raise."""
    from benchmark import afmoe_scopes, spec

    reader = spec.module("benchmark", "metrics", name)
    assert reader.read(_run()) is None
    nothing = {s: 0.0 for s in afmoe_scopes.SCOPES}
    assert reader.read(_run(nothing)) is None
    assert reader.read(_run(nothing, counters={"n:1": {"counters": {}}})) \
        is None


def test_benchmark_trinity_metrics_read_the_scopes_and_the_counters():
    from benchmark.metrics import (attention_gate_device_ms,
                                   gated_attention_device_ms,
                                   gated_attention_share_pct,
                                   moe128_experts_device_ms,
                                   moe128_experts_roofline_pct,
                                   moe128_route_device_ms,
                                   nope_full_blocks_device_ms,
                                   post_norm_device_ms,
                                   swa2048_blocks_device_ms,
                                   swa2048_blocks_roofline_pct)

    scope_s = {"attention": 0.5, "qk_norm_rope": 0.05,
               "attention_gate": 0.04, "window_attention": 0.15,
               "full_attention": 0.1, "post_norm": 0.03, "moe_router": 0.01,
               "moe_dispatch": 0.02, "moe_combine": 0.03,
               "moe_experts": 0.06, "ragged-dot": 0.04}
    counters = {"node:1": {"counters": {"moe_local_slots_total": 10 * 32768,
                                        "trainer_steps_total": 10}},
                "driver:2": {"counters": {}}}
    run = _run(scope_s, counters=counters)
    assert gated_attention_device_ms.read(run) == pytest.approx(100.0)
    assert gated_attention_share_pct.read(run) == pytest.approx(
        100 * 100 / 400)
    assert attention_gate_device_ms.read(run) == pytest.approx(8.0)
    assert post_norm_device_ms.read(run) == pytest.approx(6.0)
    assert swa2048_blocks_device_ms.read(run) == pytest.approx(30.0)
    assert nope_full_blocks_device_ms.read(run) == pytest.approx(20.0)
    assert moe128_experts_device_ms.read(run) == pytest.approx(20.0)
    assert moe128_route_device_ms.read(run) == pytest.approx(12.0)
    # 3.37 TFLOP at 197 TFLOP/s are 17.09 ms of the scope's 30 (1.81 GB are
    # 2.21 ms at 819 GB/s): compute bound
    share = swa2048_blocks_roofline_pct.read(run)
    assert share == pytest.approx(
        100 * (4 * 841_872_310_272 / 197e12) / 30e-3)
    assert 56 < share < 58
    assert any(n.startswith("swa2048_blocks_roofline_pct: compute bound")
               for n in run["notes"])
    # 32,768 slots a step: 1.24 TFLOP are 6.28 ms, the weights' 4.83 GB are
    # 5.90 ms: compute bound, of the scope's and the compiler's kernels' 20
    share = moe128_experts_roofline_pct.read(run)
    assert share == pytest.approx(
        100 * (6 * EXPERT * 32768 / 197e12) / 20e-3)
    assert any("32768.0 local slots a step" in n for n in run["notes"])
    # at the even share (8,192 slots) the weights' bytes bound it
    few = _run(scope_s, counters={"n": {"counters": {
        "moe_local_slots_total": 8192, "trainer_steps_total": 1}}})
    assert moe128_experts_roofline_pct.read(few) == pytest.approx(
        100 * (4_831_838_208 / 819e9) / 20e-3)
    assert any(n.startswith("moe128_experts_roofline_pct: memory bound")
               for n in few["notes"])
    # a kernel at the bound reads 100, and nothing is clipped on the way
    fast = _run(dict(scope_s,
                     window_attention=5 * 4 * 841_872_310_272 / 197e12))
    assert swa2048_blocks_roofline_pct.read(fast) == pytest.approx(100.0)
    # another configuration's work.py has no window_attention_work
    other = _run(scope_s, cell="lfm2_8b_a1b_packed_8k", config="lfm2_8b_a1b")
    assert swa2048_blocks_roofline_pct.read(other) is None


def test_benchmark_trinity_cell_reads_the_counters_its_program_writes():
    """``loss_tokens_per_s_chip`` (granite's cell's, whose ``workloads``
    this cell joins) from the counters ``afmoe.batch_counters`` writes a
    step; the pairs the two masks admit on the same row — seven documents,
    of which two are longer than the window of 2,048 — and the gate's
    cells."""
    from benchmark.configs.trinity_mini import program
    from benchmark.metrics import loss_tokens_per_s_chip
    from tensorflowonspark_tpu.models import afmoe

    lengths = [1200, 600, 16, 2400, 900, 2100, 976]
    seg = np.repeat(np.arange(7), lengths)
    assert seg.size == 8192
    config = program.model_config(_config())
    step = afmoe.batch_counters({"segment_ids": seg[None]}, config)
    assert step["lm_loss_tokens_total"] == 8192 - 7
    assert step["attention_full_pairs_total"] == sum(
        n * (n + 1) // 2 for n in lengths)
    assert step["attention_window_pairs_total"] == 4 * sum(
        n * (n + 1) // 2 if n <= 2048 else 2048 * 2049 // 2 + 2048 * (n - 2048)
        for n in lengths)
    assert step["attention_gate_cells_total"] == 8192 * 4096 * 4
    run = _run()
    run["trainer"]["window"] = {"steps": 100, "seconds": 30.0}
    run["_program"]["counters"] = {"chief": {"counters": {
        "lm_loss_tokens_total": 107.0 * step["lm_loss_tokens_total"],
        "trainer_steps_total": 107.0}}}
    assert loss_tokens_per_s_chip.read(run) == pytest.approx(
        8185 * 100 / 30)
    run["_program"]["counters"] = None      # a program that wrote none
    assert loss_tokens_per_s_chip.read(run) is None


def test_benchmark_trinity_scopes_read_a_trace_of_a_program_without_them():
    """``afmoe_scopes.reduced`` through its child process on a recorded v5e
    trace of the tiny ResNet step: every scope reads zero seconds, the
    readers return nothing, nothing raises — what a parent that lacks the
    model leaves this PR's readers with."""
    from benchmark import afmoe_scopes
    from benchmark.metrics import (gated_attention_share_pct,
                                   moe128_experts_roofline_pct,
                                   swa2048_blocks_roofline_pct)

    run = _run()
    del run["_afmoe_scopes"]
    run["trainer"]["trace"]["file"] = FIXTURE_TRACE
    out = afmoe_scopes.reduced(run)
    assert out["steps"] > 0
    assert out["scope_s"] == {s: 0.0 for s in afmoe_scopes.SCOPES}
    assert afmoe_scopes.reduced(run) is out         # read once
    for reader in (gated_attention_share_pct, moe128_experts_roofline_pct,
                   swa2048_blocks_roofline_pct):
        assert reader.read(run) is None
    assert any("by scope" in n for n in run["notes"])
    assert {"attention_gate", "post_norm", "embed_scale"} <= set(
        afmoe_scopes.SCOPES)
    gone = _run()
    del gone["_afmoe_scopes"]
    gone["trainer"]["trace"]["file"] = FIXTURE_TRACE + ".absent"
    assert afmoe_scopes.reduced(gone) is None


def test_benchmark_trinity_scopes_are_found_as_words():
    """``device_scopes`` finds a scope as a word of an ``op_name``:
    ``attention`` is not found in ``attention_gate`` or in the two kinds of
    blocks, which nest in it, nor they in one another."""
    import re

    word = {s: re.compile(rf"\b{re.escape(s)}\b")
            for s in ("attention", "attention_gate", "window_attention",
                      "full_attention", "post_norm")}
    inner = "jit(step)/transpose(jvp(attention))/attention_gate/mul"
    assert word["attention"].search(inner)
    assert word["attention_gate"].search(inner)
    assert not word["window_attention"].search(inner)
    alone = "jit(step)/jvp(attention_gate)/logistic"
    assert not word["attention"].search(alone)
    assert word["attention_gate"].search(alone)
    assert not word["attention"].search("jit(step)/jvp(post_norm)/rsqrt")
