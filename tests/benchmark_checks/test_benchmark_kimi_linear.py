"""The ``kimi_linear_48b_a3b`` configuration's file against the published
row, its arithmetic leaf by leaf, its ``work.py`` against figures worked by
hand, its traffic mix, the entries it adds to ``BENCHMARK.json`` and the
readers of the metrics it brings (``benchmark/kda_scopes.py``)."""

import json
import os

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURE_TRACE = os.path.join(REPO, "tests", "benchmark_checks", "fixtures",
                             "tiny_resnet_v5e.xplane.pb.gz")

#: ``config`` of the catalog's row for Kimi-Linear-48B-A3B-Instruct (its
#: ``config.json``)
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
REDUCED = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 20480}

CONFIG = "kimi_linear_48b_a3b"
CELL = "kimi_linear_packed_8k"
EXPERT = 3 * 2304 * 1024
KDA = (3 * 2304 * 4096 + 3 * 4 * 4096 + 2304 * 128 + 128 * 4096 + 4096 + 32
       + 2304 * 32 + 2304 * 128 + 128 * 4096 + 128 + 4096 * 2304)
LATENT = (2304 * 32 * 192 + 2304 * 576 + 512 + 512 * 32 * 256
          + 32 * 128 * 2304)
NEW_METRICS = ("kda_mixer_device_ms", "kda_mixer_share_pct",
               "kda_scan_device_ms", "kda_scan_roofline_pct")
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


def test_benchmark_kimi_every_width_is_the_published_one():
    config = _config()
    for key, value in PUBLISHED.items():
        assert config[key] == REDUCED.get(key, value), key
        assert type(config[key]) is type(REDUCED.get(key, value)), key
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    entry = _spec_entry("configs", CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size", "dataset"]
    assert set(config["reduced"]) == set(entry["reduced"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["source"].startswith(
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
        "blob/main/config.json")
    assert "arXiv:2510.26692" in entry["source"]
    assert config["experts_held"] == list(range(8))
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    for choice in ("kda_low_rank", "kda_decay", "kda_normalisation",
                   "kda_chunk", "head_dim", "nope", "gate_normalisation",
                   "bias_update_speed", "documents", "initialisation",
                   "optimizer", "router_precision", "document_lengths",
                   "recomputation"):
        assert config["assumed"][choice], choice
    assert config["bias_update_speed"] == 0.001 and config["init_std"] == 0.02
    assert config["optimizer"]["learning_rate"] == 1e-6
    assert config["kda_chunk"] == 64 and config["dtype"] == "bfloat16"
    for words in ("each layer shared by 32 chips", "8 experts a chip",
                  "vocabulary-parallel over 8", "first pipeline stage",
                  "no exchange runs on one chip and none is stood in for"):
        assert words in config["deployment"], words


def test_benchmark_kimi_parameter_count_is_the_issues_arithmetic():
    from benchmark.configs.kimi_linear_48b_a3b import reference

    config = _config()
    shapes = reference.leaf_shapes(config)
    count = {name: int(np.prod(shape)) for name, (shape, _) in shapes.items()}

    def layer(prefix):
        return sum(n for name, n in count.items() if name.startswith(prefix))

    assert EXPERT == 7_077_888 and 8 * EXPERT == 56_623_104
    assert KDA == (3 * 9_437_184 + 3 * 16_384 + 294_912 + 524_288 + 4_096
                   + 32 + 73_728 + 294_912 + 524_288 + 128 + 9_437_184) \
        == 39_514_272
    assert LATENT == (14_155_776 + 1_327_104 + 512 + 4_194_304
                      + 9_437_184) == 29_114_880
    routed = 8 * EXPERT + EXPERT + 2304 * 256
    assert routed == 64_290_816
    assert layer("l00/") == KDA + 4_608 + 3 * 2304 * 9216 == 103_219_872
    for i in (1, 2, 4):
        assert layer(f"l{i:02d}/") == KDA + 4_608 + routed == 103_809_696
    assert layer("l03/") == LATENT + 4_608 + routed == 93_410_304
    assert count["embed"] == count["head"] == 20480 * 2304 == 47_185_920
    assert count["final_norm"] == 2_304
    assert sum(count.values()) == config["parameters"] == 602_433_408
    assert config["bytes_per_parameter"] * config["parameters"] \
        == 9_638_934_528
    stds = reference.init_stds(config)
    assert stds["normal"] == 0.02
    assert stds["normal_out"] == pytest.approx(0.02 / 54 ** 0.5)
    kinds = {}
    for name, (_, kind) in shapes.items():
        kinds.setdefault(kind, set()).add(name.split("/")[-1])
    assert kinds["normal_out"] == {"kda_wo", "wo", "mlp_down", "shared_down",
                                   "experts_down"}
    assert kinds["taps"] == {"kda_q_conv", "kda_k_conv", "kda_v_conv"}
    assert kinds["a_log"] == {"kda_A_log"}
    assert kinds["dt_bias"] == {"kda_dt_bias"}
    seed = 2 ** 31 + 9
    taps = np.asarray(reference.make_leaf(config, seed, "l00/kda_k_conv"))
    assert taps.shape == (4, 4096) and np.abs(taps).max() <= 0.5
    assert 0.27 < taps.std() < 0.31          # uniform: bound / sqrt(3)
    a_log = np.asarray(reference.make_leaf(config, seed, "l01/kda_A_log"))
    assert a_log.shape == (32,) and 0 <= a_log.min() \
        and a_log.max() <= np.log(16)
    dt = np.log1p(np.exp(np.asarray(
        reference.make_leaf(config, seed, "l01/kda_dt_bias"), np.float64)))
    assert dt.shape == (4096,) and 0.000999 < dt.min() and dt.max() < 0.1001
    # a whole published expert layer, were all 256 experts here: 29 GB
    assert 256 * EXPERT == 1_811_939_328


def test_benchmark_kimi_program_builds_the_published_shapes():
    from benchmark.configs.kimi_linear_48b_a3b import program, reference
    from tensorflowonspark_tpu.models import kimi_linear

    config = _config()
    model = program.model_config(config)
    assert kimi_linear.parameter_count(model) == config["parameters"]
    assert model.num_experts == 256 and model.experts_held == tuple(range(8))
    assert [m for _, m, _ in kimi_linear.layer_kinds(model)] == [
        "kda", "kda", "kda", "full_attention", "kda"]
    assert [f for _, _, f in kimi_linear.layer_kinds(model)] == [
        "dense"] + ["experts"] * 4
    assert (model.kda_num_heads, model.kda_head_dim, model.qk_head_dim,
            model.v_head_dim, model.kda_chunk, model.dtype) == (
        32, 128, 192, 128, 64, "bfloat16")
    assert {program.program_name(k): tuple(s) for k, (s, _) in
            reference.leaf_shapes(config).items()} == \
        kimi_linear.leaf_shapes(model)
    assert kimi_linear.collection_shapes(model)["bias"] == ((4, 256),
                                                             "float32")
    for broken in (dict(config, experts_held=[0, 1]),
                   dict(config, q_lora_rank=1536),
                   dict(config, mla_use_nope=False),
                   dict(config, moe_layer_freq=2),
                   dict(config, tie_word_embeddings=True)):
        with pytest.raises(ValueError):
            program.model_config(broken)
    from tensorflowonspark_tpu.parallel import moe

    # a 1/32 share: three times the even share of a row's 65,536 slots
    assert moe.prefix_rows(8 * 8192, 8, 256) == 6_144


def test_benchmark_kimi_operations_match_the_hand_worked_figures():
    from benchmark.configs.kimi_linear_48b_a3b import work

    config = _config()
    assert work.mixers(config) == ["kda", "kda", "kda", "full_attention",
                                   "kda"]
    assert work.kda_parameters(config) == (
        3 * 9_437_184 + 2 * (294_912 + 524_288) + 73_728 + 9_437_184) \
        == 39_460_864
    assert work.attention_parameters(config) == 29_114_368
    assert work.expert_parameters(config) == EXPERT
    assert work.expert_layers(config) == 4
    # by hand: four KDA mixers, one latent attention, the dense SwiGLU, four
    # routers and shared experts, the untied head
    by_hand = (4 * 39_460_864 + 29_114_368 + 3 * 2304 * 9216
               + 4 * (2304 * 256 + EXPERT) + 20480 * 2304)
    assert by_hand == 328_515_584
    assert work.matmul_parameters(config) == by_hand
    step = work.step_work(config, 1)
    assert step["flops"] == 6 * by_hand * 8192 == 16_147_197_984_768
    assert step["bytes"] == 2 * 4 * 8192 + 28 * 602_433_408
    assert step["examples"] == 1
    # the routed experts: a row's 65,536 slots, 1/32 of them here
    routed = work.experts_work(config, 2048)
    assert routed["flops"] == 6 * EXPERT * 2048 == 86_973_087_744
    assert routed["bytes"] == 3 * 4 * 8 * EXPERT * 4 == 2_717_908_992
    assert work.experts_work(config, 0)["flops"] == 0
    # one chunk of 64 tokens of one head of 128 x 128, forward, by hand:
    # q k^T and k k^T (2 x 64 x 64 x 128 each, a multiply and an add), the
    # solve's 64 x 64 / 2 pairs of rows by 256 right-hand sides, W S, K^T U
    # and Q S (2 x 64 x 128 x 128 each), the pairwise terms by U
    one = (2 * (2 * 64 * 64 * 128) + 2 * (64 * 64 // 2) * 256
           + 3 * (2 * 64 * 128 * 128) + 2 * 64 * 64 * 128)
    assert one == 2_097_152 + 1_048_576 + 6_291_456 + 1_048_576 == 10_485_760
    assert work.kda_chunk_flops(64, 128, 128) == one
    scan = work.kda_work(config, 1)
    # 128 chunks x 32 heads x 4 layers, four passes
    assert scan["flops"] == 4 * one * 128 * 32 * 4 == 687_194_767_360
    # a token of a head: q, k, v bfloat16, the decay, beta and o float32
    assert scan["bytes"] == 4 * 32 * (3 * 128 * 2 + 4 * 128 + 4 + 4 * 128) \
        * 8192 * 4 == 7_532_969_984


def test_benchmark_kimi_traffic_differs_from_lfm2s_in_the_vocabulary_alone():
    def traffic(name):
        with open(os.path.join(REPO, "benchmark", "traffic",
                               name + ".json")) as f:
            return json.load(f)

    mine = traffic("tfrecord_packed_docs_8k_v20480")
    theirs = traffic("tfrecord_packed_docs_8k_v16384")
    assert "20,480" in mine.pop("note") and theirs.pop("note")
    assert mine.pop("vocab") == 20480 and theirs.pop("vocab") == 16384
    assert mine == theirs
    assert (mine["warmup_steps"], mine["trace_after_steps"],
            mine["trace_steps"], mine["batch_per_chip"]) == (3, 8, 5, 1)
    cell = _spec_entry("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "tfrecord_packed_docs_8k_v20480", 1)
    assert "1/32" in cell["why"] and len(cell["why"]) <= 200
    from benchmark.traffic import packed_documents

    full = traffic("tfrecord_packed_docs_8k_v20480")
    rows = packed_documents.rows(full, 2 ** 31 + 7, [0, 1023])
    assert rows["tokens"].shape == (2, 8192)
    assert 0 <= rows["tokens"].min() and rows["tokens"].max() < 20480


#: PR 40's per-layer metrics: ``test_benchmark_lfm2.py`` holds them to be
#: the *last* of ``BENCHMARK.json``'s lists, which an appended cell makes
#: false (``tests/conftest.py::STALE``); what that test holds besides is held
#: here, for its cell and for this one
LFM2_METRICS = ("conv_mixer_device_ms", "conv_mixer_share_pct",
                "short_conv_roofline_pct", "gqa_attention_device_ms",
                "routed_experts_device_ms", "routed_experts_roofline_pct")
#: other cells' names for attention's, the scan's and the routed experts'
#: time, whose ``workloads`` their own tests pin to their own cells
OTHERS_METRICS = {"mla_device_ms", "ssm_scan_device_ms",
                  "moe_experts_device_ms", "attention_device_ms"}


def test_benchmark_kimi_entries_follow_the_accepted_ones_in_their_order():
    """``spec.validate`` and ``validate_files`` with the new entries; the
    accepted benchmark's entries lead theirs in the accepted order (counted
    from the front, so that a later cell's entries do not move them)."""
    from benchmark import spec

    spec_ = spec.load(REPO)
    spec.validate(spec_)
    spec.validate_files(spec_)
    assert [c["name"] for c in spec_["configs"]][:6] == [
        "resnet50", "criteo_widedeep", "granite_4_0_h_micro",
        "glm_4_7_flash", "lfm2_8b_a1b", CONFIG]
    assert [w["name"] for w in spec_["workloads"]][:6] == [
        "resnet50_fed", "widedeep_spark_fed", "granite_h_micro_packed_8k",
        "glm47_flash_packed_8k", "lfm2_8b_a1b_packed_8k", CELL]
    names = [m["name"] for m in spec_["per_layer"]]
    at = names.index(LFM2_METRICS[0])
    assert tuple(names[at:at + 10]) == LFM2_METRICS + NEW_METRICS
    for name in ALL_CELL_METRICS:
        assert _spec_entry("per_layer", name)["workloads"][:6] == [
            w["name"] for w in spec_["workloads"]][:6]
    # the counter that granite's cell reads serves every packed-row cell's
    # program; this cell is the second to list it
    assert _spec_entry("per_layer", "loss_tokens_per_s_chip")[
        "workloads"][:2] == ["granite_h_micro_packed_8k", CELL]


@pytest.mark.parametrize("cell,config,brought,absent", [
    ("lfm2_8b_a1b_packed_8k", "lfm2_8b_a1b", LFM2_METRICS,
     OTHERS_METRICS | set(NEW_METRICS)),
    (CELL, CONFIG, NEW_METRICS + ALL_CELL_METRICS
     + ("loss_tokens_per_s_chip",),
     OTHERS_METRICS | set(LFM2_METRICS)),
])
def test_benchmark_cell_reports_its_own_metrics_and_has_its_limits(
        cell, config, brought, absent):
    """A packed-row expert cell's per-layer metrics (its own and every
    cell's, none under another cell's name), its configuration's package
    and the five limits its ``correct`` is decided by, with the control's
    readings beside them: for PR 40's cell what its own stale test held,
    and the same for this one."""
    from benchmark import spec

    spec_ = spec.load(REPO)
    mine = {m["name"] for m in spec.metrics_of(spec_, cell, "per_layer")}
    assert set(brought) <= mine
    assert {"step_device_ms", "step_roofline_pct", "feed_wait_ms"} <= mine
    assert not mine & absent
    assert spec.cell(spec_, cell)["config_package"] == (
        "benchmark.configs." + config)
    with open(os.path.join(REPO, "benchmark", "configs", config,
                           "limits.json")) as f:
        limits = json.load(f)
    assert set(limits["limits"]) == {
        "loss_step1_rel", "loss_step2_rel", "loss_step3_rel",
        "first_grad_norm_gap", "param_change_norm_gap"}
    assert "control" in limits["readings"].lower()


def _run(kda_scope_s=None, steps=5, cell=CELL, config=CONFIG):
    with open(os.path.join(REPO, "benchmark", "configs", config,
                           "config.json")) as f:
        values = json.load(f)
    run = {"cell": {"name": cell, "chips": 1,
                    "config_package": "benchmark.configs." + config,
                    "config_values": values,
                    "traffic_values": {"batch_per_chip": 1}},
           "trainer": {"trace": {"busy_s": 2.0, "steps": steps}},
           "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
           "notes": [], "_kda_scopes": None}
    if kda_scope_s is not None:
        run["_kda_scopes"] = {"steps": steps, "scope_s": kda_scope_s,
                              "top_ops": []}
    return run


@pytest.mark.parametrize("name", NEW_METRICS)
def test_benchmark_kimi_metric_is_left_out_where_there_is_nothing_to_read(
        name):
    """An untraced run, a program without the scopes (the parent of the PR
    that brought them) and another cell's configuration: None, never a
    raise."""
    from benchmark import kda_scopes, spec

    reader = spec.module("benchmark", "metrics", name)
    assert reader.read(_run()) is None
    assert reader.read(_run({s: 0.0 for s in kda_scopes.SCOPES})) is None
    other = _run({s: 0.0 for s in kda_scopes.SCOPES},
                 cell="lfm2_8b_a1b_packed_8k", config="lfm2_8b_a1b")
    assert reader.read(other) is None
    entry = _spec_entry("per_layer", name)
    assert entry["workloads"] == [CELL]
    assert (entry["moves"], entry["source"], entry["layer"]) == (
        "examples_per_s_chip", "device_trace", "kernels")
    assert entry["unit"] == ("%" if name.endswith("_pct") else "ms")


def test_benchmark_kimi_metrics_read_the_scopes():
    from benchmark.metrics import (kda_mixer_device_ms, kda_mixer_share_pct,
                                   kda_scan_device_ms, kda_scan_roofline_pct)

    scope_s = {"kda_mixer": 1.0, "kda_project": 0.3, "kda_conv": 0.2,
               "kda_scan": 0.4, "kda_out": 0.1}
    run = _run(scope_s)
    assert kda_mixer_device_ms.read(run) == pytest.approx(200.0)
    assert kda_mixer_share_pct.read(run) == pytest.approx(100 * 200 / 400)
    assert kda_scan_device_ms.read(run) == pytest.approx(80.0)
    # 7.53 GB at 819 GB/s is 9.198 ms of the scope's 80 (687 GFLOP are 3.49
    # ms at the peak): memory bound
    share = kda_scan_roofline_pct.read(run)
    assert share == pytest.approx(100 * (7_532_969_984 / 819e9) / 80e-3)
    assert 11 < share < 12
    assert any(n.startswith("kda_scan_roofline_pct: memory bound")
               for n in run["notes"])
    # a scan at the bound reads 100, and nothing is clipped on the way
    fast = _run(dict(scope_s, kda_scan=5 * 7_532_969_984 / 819e9))
    assert kda_scan_roofline_pct.read(fast) == pytest.approx(100.0)
    # another configuration's work.py has no kda_work: left out
    other = _run(scope_s, cell="lfm2_8b_a1b_packed_8k", config="lfm2_8b_a1b")
    assert kda_scan_roofline_pct.read(other) is None


def test_benchmark_kimi_cell_reads_the_loss_tokens_its_program_counts():
    """``loss_tokens_per_s_chip`` (granite's cell's, whose ``workloads``
    this cell joins) from the counters ``kimi_linear.batch_counters`` writes
    a step: a row of 8,192 tokens in seven documents bears 8,185 losses; 60
    steps in a 30 s window."""
    from benchmark.metrics import loss_tokens_per_s_chip
    from tensorflowonspark_tpu.models import kimi_linear

    seg = np.repeat(np.arange(7), [1200, 600, 16, 2400, 900, 2000, 1076])
    assert seg.size == 8192
    step = kimi_linear.batch_counters({"segment_ids": seg[None]},
                                      kimi_linear.Config.tiny())
    assert step["lm_loss_tokens_total"] == 8192 - 7
    run = _run()
    run["trainer"]["window"] = {"steps": 60, "seconds": 30.0}
    run["_program"] = {"spans": {}, "dropped": 0, "counters": {
        "chief": {"counters": {
            "lm_loss_tokens_total": 67.0 * step["lm_loss_tokens_total"],
            "trainer_steps_total": 67.0}}}}
    assert loss_tokens_per_s_chip.read(run) == pytest.approx(8185 * 60 / 30)
    run["_program"]["counters"] = None      # a program that wrote none
    assert loss_tokens_per_s_chip.read(run) is None


def test_benchmark_kimi_kda_scopes_read_a_trace_of_a_program_without_them():
    """``kda_scopes.reduced`` through its child process on a recorded v5e
    trace of the tiny ResNet step: every scope reads zero seconds, the
    readers return nothing, nothing raises — what a parent that lacks the
    model leaves this PR's readers with."""
    from benchmark import kda_scopes
    from benchmark.metrics import kda_mixer_device_ms, kda_scan_roofline_pct

    run = _run()
    del run["_kda_scopes"]
    run["trainer"]["trace"]["file"] = FIXTURE_TRACE
    out = kda_scopes.reduced(run)
    assert out["steps"] > 0
    assert out["scope_s"] == {s: 0.0 for s in kda_scopes.SCOPES}
    assert kda_scopes.reduced(run) is out          # read once
    assert kda_mixer_device_ms.read(run) is None
    assert kda_scan_roofline_pct.read(run) is None
    assert any("KDA mixers' scopes" in n for n in run["notes"])
    assert kda_scopes.SCOPES[:5] == ("kda_mixer", "kda_project", "kda_conv",
                                     "kda_scan", "kda_out")
    gone = _run()
    del gone["_kda_scopes"]
    gone["trainer"]["trace"]["file"] = FIXTURE_TRACE + ".absent"
    assert kda_scopes.reduced(gone) is None
