"""The ``mellum2_12b_a2_5b`` configuration's file against the published row,
its arithmetic leaf by leaf, its ``work.py`` against figures worked by hand,
its traffic mix, the entries it adds to ``BENCHMARK.json`` and the readers of
the metrics it brings (``benchmark/swa_scopes.py``)."""

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
#: ``config`` of the catalog's row for Mellum2-12B-A2.5B-Instruct (its
#: ``config.json``)
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}
REDUCED = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 24576}

CONFIG = "mellum2_12b_a2_5b"
CELL = "mellum2_packed_8k"
EXPERT = 3 * 2304 * 896
ATTENTION = 2 * 2304 * 32 * 128 + 2 * 2304 * 4 * 128
NEW_METRICS = ("window_attention_device_ms", "full_attention_device_ms",
               "window_attention_roofline_pct", "attention_mixer_share_pct")
ALL_CELL_METRICS = ("device_idle_pct", "idle_feed_pct", "idle_h2d_pct",
                    "idle_host_pct", "h2d_wait_ms", "device_step_est_ms",
                    "device_step_est_err_pct", "step_enqueue_pct",
                    "h2d_transfer_ms")
KIMI_METRICS = ("kda_mixer_device_ms", "kda_mixer_share_pct",
                "kda_scan_device_ms", "kda_scan_roofline_pct")


def _config():
    with open(os.path.join(REPO, "benchmark", "configs", CONFIG,
                           "config.json")) as f:
        return json.load(f)


def _spec():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _spec_entry(group, name):
    return next(e for e in _spec()[group] if e["name"] == name)


def test_benchmark_mellum2_every_width_is_the_published_one():
    config = _config()
    for key, value in PUBLISHED.items():
        assert config[key] == REDUCED.get(key, value), key
        assert type(config[key]) is type(REDUCED.get(key, value)), key
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    if os.path.isfile(CATALOG):     # where the catalog is at hand
        with open(CATALOG) as f:
            row, = [r for r in map(json.loads, f)
                    if r["name"] == "Mellum2-12B-A2.5B-Instruct"]
        assert row["config"] == PUBLISHED
        assert config["source"].startswith(row["source_url"])
    entry = _spec_entry("configs", CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size", "dataset"]
    assert set(config["reduced"]) == set(entry["reduced"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["source"].startswith(
        "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
        "blob/main/config.json")
    assert config["experts_held"] == list(range(16))
    assert config["layers_run"] == [0, 1, 2, 3]
    assert [config["layer_types"][i] for i in config["layers_run"]] == [
        SLIDING, SLIDING, SLIDING, FULL]
    assert config["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    for choice in ("qk_norm", "router_scores", "auxiliary_loss",
                   "prediction_module", "window", "documents",
                   "mlp_layer_types", "initialisation", "optimizer",
                   "router_precision", "document_lengths", "recomputation"):
        assert config["assumed"][choice], choice
    assert config["init_std"] == 0.02 and config["dtype"] == "bfloat16"
    assert config["embed_init_std"] == 1.0
    assert "departs from the issue's letter" in config["assumed"][
        "initialisation"]
    assert config["optimizer"]["learning_rate"] == 1e-6
    assert config["control_precision"] == "float8"
    for words in ("each layer shared by 4 chips", "16 experts a chip",
                  "vocabulary-parallel over the same 4",
                  "first pipeline stage",
                  "no exchange runs on one chip and none is stood in for"):
        assert words in config["deployment"], words


def test_benchmark_mellum2_parameter_count_is_the_issues_arithmetic():
    from benchmark.configs.mellum2_12b_a2_5b import reference

    config = _config()
    shapes = reference.leaf_shapes(config)
    count = {name: int(np.prod(shape)) for name, (shape, _) in shapes.items()}

    def layer(prefix):
        return sum(n for name, n in count.items() if name.startswith(prefix))

    assert EXPERT == 6_193_152 and 16 * EXPERT == 99_090_432
    assert ATTENTION == 9_437_184 + 2 * 1_179_648 + 9_437_184 == 21_233_664
    for i in range(4):
        pre = f"l{i:02d}/"
        assert count[pre + "wq"] == count[pre + "wo"] == 9_437_184
        assert count[pre + "wk"] == count[pre + "wv"] == 1_179_648
        assert count[pre + "q_norm"] == count[pre + "k_norm"] == 128
        assert count[pre + "norm1"] == count[pre + "norm2"] == 2_304
        assert count[pre + "router"] == 147_456
        for leaf in ("experts_gate", "experts_up", "experts_down"):
            assert count[pre + leaf] == 16 * 2304 * 896 == 33_030_144
        assert layer(pre) == ATTENTION + 256 + 4_608 + 147_456 \
            + 16 * EXPERT == 120_476_416
    assert count["embed"] == count["head"] == 24576 * 2304 == 56_623_104
    assert count["final_norm"] == 2_304
    assert sum(count.values()) == config["parameters"] == 595_154_176 \
        == 4 * 120_476_416 + 113_246_208 + 2_304
    assert config["bytes_per_parameter"] * config["parameters"] \
        == 9_522_466_816
    # the whole published model by the same equations: the published 12B
    whole_layer = ATTENTION + 256 + 4_608 + 147_456 + 64 * EXPERT
    assert whole_layer == 417_747_712
    assert 28 * whole_layer + 2 * 98304 * 2304 + 2304 == 12_149_923_072
    stds = reference.init_stds(config)
    assert stds["normal"] == 0.02 and stds["normal_embed"] == 1.0
    assert stds["normal_out"] == pytest.approx(0.02 / 56 ** 0.5)
    kinds = {}
    for name, (_, kind) in shapes.items():
        kinds.setdefault(kind, set()).add(name.split("/")[-1])
    assert kinds["normal_out"] == {"wo", "experts_down"}
    assert kinds["normal_embed"] == {"embed"}
    assert kinds["ones"] == {"norm1", "norm2", "q_norm", "k_norm",
                             "final_norm"}
    seed = 2 ** 31 + 9
    wk = np.asarray(reference.make_leaf(config, seed, "l03/wk"))
    assert wk.shape == (2304, 512) and 0.0195 < wk.std() < 0.0205
    rows = np.asarray(reference.make_leaf(config, seed, "embed"))[:512]
    assert 0.98 < rows.std() < 1.02


def test_benchmark_mellum2_program_builds_the_published_shapes():
    from benchmark.configs.mellum2_12b_a2_5b import program, reference
    from tensorflowonspark_tpu.models import mellum_moe
    from tensorflowonspark_tpu.parallel import moe

    config = _config()
    model = program.model_config(config)
    assert mellum_moe.parameter_count(model) == config["parameters"]
    assert model.num_experts == 64 and model.experts_held == tuple(range(16))
    assert [m for _, m, _ in mellum_moe.layer_kinds(model)] == [
        SLIDING, SLIDING, SLIDING, FULL]
    assert {f for _, _, f in mellum_moe.layer_kinds(model)} == {"experts"}
    assert (model.num_attention_heads, model.num_key_value_heads,
            model.head_dim, model.sliding_window, model.dtype) == (
        32, 4, 128, 1024, "bfloat16")
    assert model.rope_parameters == PUBLISHED["rope_parameters"]
    assert {program.program_name(k): tuple(s) for k, (s, _) in
            reference.leaf_shapes(config).items()} == \
        mellum_moe.leaf_shapes(model)
    assert mellum_moe.collection_shapes(model)["bias"] == ((4, 64),
                                                           "float32")
    routing = mellum_moe.routing(model)
    assert (routing.score, routing.top_k, routing.speed) == ("softmax", 8,
                                                             0.0)
    for broken in (dict(config, experts_held=[0, 1]),
                   dict(config, layers_run=[0, 1]),
                   dict(config, tie_word_embeddings=True),
                   dict(config, attention_bias=True),
                   dict(config, mlp_layer_types=["dense"] + ["sparse"] * 27),
                   dict(config, use_sliding_window=False)):
        with pytest.raises(ValueError):
            program.model_config(broken)
    # a quarter share: three times the even share of a row's 65,536 slots
    assert moe.prefix_rows(8 * 8192, 16, 64) == 49_152


def test_benchmark_mellum2_operations_match_the_hand_worked_figures():
    from benchmark.configs.mellum2_12b_a2_5b import work

    config = _config()
    assert work.mixers(config) == [SLIDING, SLIDING, SLIDING, FULL]
    assert work.attention_parameters(config) == ATTENTION
    assert work.expert_parameters(config) == EXPERT
    assert work.expert_layers(config) == 4
    # by hand: four layers' projections and routers, the untied head
    by_hand = 4 * (ATTENTION + 2304 * 64) + 24576 * 2304
    assert by_hand == 142_147_584
    assert work.matmul_parameters(config) == by_hand
    step = work.step_work(config, 1)
    assert step["flops"] == 6 * by_hand * 8192 == 6_986_838_048_768
    assert step["bytes"] == 2 * 4 * 8192 + 28 * 595_154_176
    assert step["examples"] == 1
    # the routed experts: a row's 65,536 slots, a quarter of them here
    routed = work.experts_work(config, 16384)
    assert routed["flops"] == 6 * EXPERT * 16384 == 608_811_614_208
    assert routed["bytes"] == 3 * 4 * 16 * EXPERT * 4 == 4_756_340_736
    assert work.experts_work(config, 0)["flops"] == 0
    # the triangle of a row of 8,192 and the band of 1,024 under it, by
    # hand: a query at place i holds min(i + 1, 1,024) keys
    triangle = 8192 * 8193 // 2
    band = sum(min(i + 1, 1024) for i in range(8192))
    assert (triangle, band) == (33_558_528, 7_864_832)
    assert work.mask_pairs(8192) == triangle
    assert work.mask_pairs(8192, 1024) == band == 8192 * 1024 - 1024 * 1023 // 2
    assert work.mask_pairs(600, 1024) == 600 * 601 // 2
    # forward, a layer: two products of 2 operations a pair a number of a
    # head's 128, 32 heads; a step makes them 4.5 times (forward, the
    # recomputation, the backward pass's five products)
    full = work.full_attention_work(config, 8192)
    assert full["flops"] == int(4.5 * 4 * triangle * 128 * 32) \
        == 2_474_203_152_384
    window = work.window_attention_work(config, 8192)
    assert window["flops"] == 3 * int(4.5 * 4 * band * 128 * 32) \
        == 3 * 579_858_333_696
    # q and o of 32 heads, k and v of 4, bfloat16: 18,432 B a token, four
    # times a step (forward, recomputation, backward with the gradients)
    assert full["bytes"] == 4 * 2 * (32 + 4) * 128 * 2 * 8192 == 603_979_776
    assert window["bytes"] == 3 * full["bytes"]
    # two rows are twice one
    assert work.window_attention_work(config, 16384)["flops"] \
        == 2 * window["flops"]


def test_benchmark_mellum2_traffic_differs_from_kimis_in_the_vocabulary_alone():
    def traffic(name):
        with open(os.path.join(REPO, "benchmark", "traffic",
                               name + ".json")) as f:
            return json.load(f)

    mine = traffic("tfrecord_packed_docs_8k_v24576")
    theirs = traffic("tfrecord_packed_docs_8k_v20480")
    assert "24,576" in mine.pop("note") and theirs.pop("note")
    assert mine.pop("vocab") == 24576 and theirs.pop("vocab") == 20480
    assert mine == theirs
    assert (mine["warmup_steps"], mine["trace_after_steps"],
            mine["trace_steps"], mine["batch_per_chip"]) == (3, 8, 5, 1)
    cell = _spec_entry("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "tfrecord_packed_docs_8k_v24576", 1)
    assert "1/4" in cell["why"] and len(cell["why"]) <= 200
    from benchmark.traffic import packed_documents

    full = traffic("tfrecord_packed_docs_8k_v24576")
    rows = packed_documents.rows(full, 2 ** 31 + 7, [0, 1023])
    assert rows["tokens"].shape == (2, 8192)
    assert 0 <= rows["tokens"].min() and rows["tokens"].max() < 24576
    # documents shorter and longer than the window in the mix's rows
    seg = rows["segment_ids"]
    lengths = np.diff(np.flatnonzero(np.r_[
        True, seg[0, 1:] != seg[0, :-1], True]))
    assert lengths.min() < 1024 < lengths.max()


def test_benchmark_mellum2_entries_follow_the_accepted_ones_in_their_order():
    """``spec.validate`` and ``validate_files`` with the new entries; the
    accepted benchmark's entries lead theirs in the accepted order (counted
    from the front, so that a later cell's entries do not move them)."""
    from benchmark import spec

    spec_ = spec.load(REPO)
    spec.validate(spec_)
    spec.validate_files(spec_)
    assert [c["name"] for c in spec_["configs"]][:7] == [
        "resnet50", "criteo_widedeep", "granite_4_0_h_micro",
        "glm_4_7_flash", "lfm2_8b_a1b", "kimi_linear_48b_a3b", CONFIG]
    assert [w["name"] for w in spec_["workloads"]][:7] == [
        "resnet50_fed", "widedeep_spark_fed", "granite_h_micro_packed_8k",
        "glm47_flash_packed_8k", "lfm2_8b_a1b_packed_8k",
        "kimi_linear_packed_8k", CELL]
    names = [m["name"] for m in spec_["per_layer"]]
    at = names.index(KIMI_METRICS[0])
    assert tuple(names[at:at + 8]) == KIMI_METRICS + NEW_METRICS
    for name in ALL_CELL_METRICS:
        assert _spec_entry("per_layer", name)["workloads"][:7] == [
            w["name"] for w in spec_["workloads"]][:7]
    assert _spec_entry("per_layer", "loss_tokens_per_s_chip")[
        "workloads"][:3] == ["granite_h_micro_packed_8k",
                             "kimi_linear_packed_8k", CELL]
    for e in spec_["configs"] + spec_["workloads"]:
        assert len(e["why"]) <= 200 and "\n" not in e["why"]
    assert not any(spec.names_a_width(k) for c in spec_["configs"]
                   for k in c["reduced"])


def test_benchmark_mellum2_cell_reports_its_own_metrics_and_has_its_limits():
    from benchmark import spec

    spec_ = spec.load(REPO)
    mine = {m["name"] for m in spec.metrics_of(spec_, CELL, "per_layer")}
    assert set(NEW_METRICS + ALL_CELL_METRICS
               + ("loss_tokens_per_s_chip",)) <= mine
    assert {"step_device_ms", "step_roofline_pct", "feed_wait_ms"} <= mine
    assert not mine & (set(KIMI_METRICS) | {
        "mla_device_ms", "ssm_scan_device_ms", "moe_experts_device_ms",
        "attention_device_ms", "gqa_attention_device_ms",
        "routed_experts_device_ms"})
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
    assert "control" in limits["readings"].lower()
    assert "window" in limits["readings"].lower()


def _run(swa_scope_s=None, steps=5, cell=CELL, config=CONFIG):
    with open(os.path.join(REPO, "benchmark", "configs", config,
                           "config.json")) as f:
        values = json.load(f)
    run = {"cell": {"name": cell, "chips": 1,
                    "config_package": "benchmark.configs." + config,
                    "config_values": values,
                    "traffic_values": {"batch_per_chip": 1}},
           "trainer": {"trace": {"busy_s": 2.0, "steps": steps}},
           "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
           "notes": [], "_swa_scopes": None}
    if swa_scope_s is not None:
        run["_swa_scopes"] = {"steps": steps, "scope_s": swa_scope_s,
                              "top_ops": []}
    return run


@pytest.mark.parametrize("name", NEW_METRICS)
def test_benchmark_mellum2_metric_is_left_out_where_there_is_nothing_to_read(
        name):
    """An untraced run, a program without the scopes (the parent of the PR
    that brought them) and another cell's configuration: None, never a
    raise."""
    from benchmark import spec, swa_scopes

    reader = spec.module("benchmark", "metrics", name)
    assert reader.read(_run()) is None
    assert reader.read(_run({s: 0.0 for s in swa_scopes.SCOPES})) is None
    other = _run({s: 0.0 for s in swa_scopes.SCOPES},
                 cell="lfm2_8b_a1b_packed_8k", config="lfm2_8b_a1b")
    assert reader.read(other) is None
    entry = _spec_entry("per_layer", name)
    assert entry["workloads"] == [CELL]
    assert (entry["moves"], entry["source"], entry["layer"]) == (
        "examples_per_s_chip", "device_trace", "kernels")
    assert entry["unit"] == ("%" if name.endswith("_pct") else "ms")


def test_benchmark_mellum2_metrics_read_the_scopes():
    from benchmark.metrics import (attention_mixer_share_pct,
                                   full_attention_device_ms,
                                   window_attention_device_ms,
                                   window_attention_roofline_pct)

    scope_s = {"attention": 0.5, "qk_norm_rope": 0.05,
               "window_attention": 0.15, "full_attention": 0.1}
    run = _run(scope_s)
    assert window_attention_device_ms.read(run) == pytest.approx(30.0)
    assert full_attention_device_ms.read(run) == pytest.approx(20.0)
    assert attention_mixer_share_pct.read(run) == pytest.approx(
        100 * 100 / 400)
    # 1.74 TFLOP at 197 TFLOP/s are 8.83 ms of the scope's 30 (1.81 GB are
    # 2.21 ms at 819 GB/s): compute bound
    share = window_attention_roofline_pct.read(run)
    assert share == pytest.approx(
        100 * (3 * 579_858_333_696 / 197e12) / 30e-3)
    assert 29 < share < 30
    assert any(n.startswith("window_attention_roofline_pct: compute bound")
               for n in run["notes"])
    # attention at the bound reads 100, and nothing is clipped on the way
    fast = _run(dict(scope_s,
                     window_attention=5 * 3 * 579_858_333_696 / 197e12))
    assert window_attention_roofline_pct.read(fast) == pytest.approx(100.0)
    # another configuration's work.py has no window_attention_work
    other = _run(scope_s, cell="lfm2_8b_a1b_packed_8k", config="lfm2_8b_a1b")
    assert window_attention_roofline_pct.read(other) is None


def test_benchmark_mellum2_cell_reads_the_loss_tokens_its_program_counts():
    """``loss_tokens_per_s_chip`` (granite's cell's, whose ``workloads``
    this cell joins) from the counters ``mellum_moe.batch_counters`` writes
    a step, and the pairs the two masks admit on the same row: seven
    documents, of which four are longer than the window of 1,024."""
    from benchmark.metrics import loss_tokens_per_s_chip
    from tensorflowonspark_tpu.models import mellum_moe

    lengths = [1200, 600, 16, 2400, 900, 2000, 1076]
    seg = np.repeat(np.arange(7), lengths)
    assert seg.size == 8192
    config = mellum_moe.Config()
    step = mellum_moe.batch_counters({"segment_ids": seg[None]}, config)
    assert step["lm_loss_tokens_total"] == 8192 - 7
    assert step["attention_full_pairs_total"] == 7 * sum(
        n * (n + 1) // 2 for n in lengths)
    assert step["attention_window_pairs_total"] == 21 * sum(
        n * (n + 1) // 2 if n <= 1024 else 1024 * 1025 // 2 + 1024 * (n - 1024)
        for n in lengths)
    run = _run()
    run["trainer"]["window"] = {"steps": 100, "seconds": 30.0}
    run["_program"] = {"spans": {}, "dropped": 0, "counters": {
        "chief": {"counters": {
            "lm_loss_tokens_total": 107.0 * step["lm_loss_tokens_total"],
            "trainer_steps_total": 107.0}}}}
    assert loss_tokens_per_s_chip.read(run) == pytest.approx(
        8185 * 100 / 30)
    run["_program"]["counters"] = None      # a program that wrote none
    assert loss_tokens_per_s_chip.read(run) is None


def test_benchmark_mellum2_swa_scopes_read_a_trace_of_a_program_without_them():
    """``swa_scopes.reduced`` through its child process on a recorded v5e
    trace of the tiny ResNet step: every scope reads zero seconds, the
    readers return nothing, nothing raises — what a parent that lacks the
    model leaves this PR's readers with."""
    from benchmark import swa_scopes
    from benchmark.metrics import (window_attention_device_ms,
                                   window_attention_roofline_pct)

    run = _run()
    del run["_swa_scopes"]
    run["trainer"]["trace"]["file"] = FIXTURE_TRACE
    out = swa_scopes.reduced(run)
    assert out["steps"] > 0
    assert out["scope_s"] == {s: 0.0 for s in swa_scopes.SCOPES}
    assert swa_scopes.reduced(run) is out          # read once
    assert window_attention_device_ms.read(run) is None
    assert window_attention_roofline_pct.read(run) is None
    assert any("attention layers' scopes" in n for n in run["notes"])
    assert swa_scopes.SCOPES[:4] == ("attention", "qk_norm_rope",
                                     "window_attention", "full_attention")
    gone = _run()
    del gone["_swa_scopes"]
    gone["trainer"]["trace"]["file"] = FIXTURE_TRACE + ".absent"
    assert swa_scopes.reduced(gone) is None


def test_benchmark_mellum2_scopes_are_found_as_words():
    """``device_scopes`` finds a scope as a word of an ``op_name``:
    ``attention`` is not found in the two kinds of blocks that nest in it,
    nor they in one another."""
    import re

    word = {s: re.compile(rf"\b{re.escape(s)}\b")
            for s in ("attention", "window_attention", "full_attention")}
    inner = "jit(step)/transpose(jvp(attention))/window_attention/" \
            "attention_backward"
    assert word["attention"].search(inner)
    assert word["window_attention"].search(inner)
    assert not word["full_attention"].search(inner)
    alone = "jit(step)/jvp(full_attention)/attention_forward"
    assert not word["attention"].search(alone)
    assert word["full_attention"].search(alone)
