"""Model zoo × Trainer on the virtual 8-device CPU mesh.

Every registered model must train (loss finite and decreasing over a few
steps on a fixed batch) and predict under its tiny config — the model-level
analogue of the reference running each example small (SURVEY.md §4).
"""

import numpy as np
import pytest

from tensorflowonspark_tpu import models as zoo
from tensorflowonspark_tpu.parallel import MeshConfig
from tensorflowonspark_tpu.trainer import Trainer


ALL_MODELS = zoo.available()


def test_registry_lists_all():
    assert ALL_MODELS == sorted(
        ["mnist_mlp", "cifar10_cnn", "resnet50", "inception_v3",
         "mobilenet_v1", "wide_deep", "bert", "tiny_lm", "granite_hybrid",
         "mla_moe", "lfm2_moe", "kimi_linear", "mellum_moe", "afmoe"]
    )


@pytest.mark.parametrize(
    "name",
    [pytest.param(n, marks=pytest.mark.slow) if n == "inception_v3"
     else n for n in ALL_MODELS])  # inception: ~85 s of compile; its
# canonical-config coverage is slow-marked below for the same reason
def test_model_trains_and_predicts(name):
    t = Trainer(name, mesh_config=MeshConfig(dp=8), learning_rate=1e-2)
    batch = t.module_lib.example_batch(t.config, batch_size=16)
    losses = [float(t.step(batch)) for _ in range(5)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    out = t.predict(batch)
    leaf = out[0] if isinstance(out, tuple) else out
    assert np.asarray(leaf).shape[0] == 16


def test_bert_ring_attention_mesh():
    """BERT over a dp×sp mesh: sequence sharded, ring attention path."""
    t = Trainer("bert", mesh_config=MeshConfig(dp=2, sp=4), learning_rate=1e-2)
    batch = t.module_lib.example_batch(t.config, batch_size=4, seq_len=16)
    losses = [float(t.step(batch)) for _ in range(3)]
    assert np.isfinite(losses).all()


def test_zero_shards_params():
    t = Trainer("mnist_mlp", mesh_config=MeshConfig(dp=2, fsdp=4), zero=True)
    batch = t.module_lib.example_batch(t.config, batch_size=16)
    t.step(batch)
    specs = [
        tuple(leaf.sharding.spec)
        for leaf in __import__("jax").tree_util.tree_leaves(t.params)
    ]
    assert any("fsdp" in str(s) for s in specs)


def test_trainer_checkpoint_roundtrip(tmp_path):
    t = Trainer("mnist_mlp", mesh_config=MeshConfig(dp=8))
    batch = t.module_lib.example_batch(t.config, batch_size=8)
    t.step(batch)
    pred_before = np.asarray(t.predict(batch))
    t.save(str(tmp_path / "ckpt"))

    t2 = Trainer("mnist_mlp", mesh_config=MeshConfig(dp=8), seed=123)
    t2.restore(str(tmp_path / "ckpt"))
    np.testing.assert_allclose(
        np.asarray(t2.predict(batch)), pred_before, rtol=1e-5
    )
    assert int(t2.state.step) == 1


def test_trainer_checkpoint_restores_across_meshes(tmp_path):
    """A checkpoint written on one mesh restores onto a DIFFERENT mesh
    (restart after resizing the cluster): restore carries the reader's
    own shardings instead of trusting the writer's recorded topology."""
    t = Trainer("mnist_mlp", mesh_config=MeshConfig(dp=8))
    batch = t.module_lib.example_batch(t.config, batch_size=8)
    t.step(batch)
    pred_before = np.asarray(t.predict(batch))
    t.save(str(tmp_path / "ckpt"))

    t2 = Trainer("mnist_mlp", mesh_config=MeshConfig(dp=2, fsdp=4), seed=7)
    t2.restore(str(tmp_path / "ckpt"))
    np.testing.assert_allclose(
        np.asarray(t2.predict(batch)), pred_before, rtol=1e-5
    )
    losses = [float(t2.step(batch)) for _ in range(2)]
    assert np.isfinite(losses).all()


def test_resnet_batchnorm_trains():
    """Config(norm="batch"): running stats ride TrainState.collections and
    update every step; eval uses the running averages."""
    from tensorflowonspark_tpu.models import resnet

    config = resnet.Config.tiny(norm="batch")
    t = Trainer("resnet50", config=config, mesh_config=MeshConfig(dp=8),
                learning_rate=1e-2)
    assert "batch_stats" in t.state.collections
    import jax

    stats0 = jax.tree_util.tree_map(
        np.asarray, t.state.collections["batch_stats"]
    )
    batch = t.module_lib.example_batch(config, batch_size=16)
    losses = [float(t.step(batch)) for _ in range(5)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    stats1 = t.state.collections["batch_stats"]
    changed = jax.tree_util.tree_map(
        lambda a, b: not np.allclose(a, np.asarray(b)), stats0, stats1
    )
    assert any(jax.tree_util.tree_leaves(changed))  # stats actually updated
    out = np.asarray(t.predict(batch))
    assert out.shape == (16, config.num_classes)


def test_resnet_batchnorm_vs_groupnorm_parity():
    """Both norms train to finite decreasing loss on the same tiny batch."""
    from tensorflowonspark_tpu.models import resnet

    results = {}
    for norm in ("group", "batch"):
        t = Trainer("resnet50", config=resnet.Config.tiny(norm=norm),
                    mesh_config=MeshConfig(dp=4, fsdp=2), learning_rate=1e-2)
        batch = t.module_lib.example_batch(t.config, batch_size=16)
        results[norm] = [float(t.step(batch)) for _ in range(4)]
    for norm, losses in results.items():
        assert np.isfinite(losses).all(), norm
        assert losses[-1] < losses[0], norm


def test_resnet_batchnorm_checkpoint_roundtrip(tmp_path):
    from tensorflowonspark_tpu.models import resnet

    config = resnet.Config.tiny(norm="batch")
    t = Trainer("resnet50", config=config, mesh_config=MeshConfig(dp=8))
    batch = t.module_lib.example_batch(config, batch_size=8)
    t.step(batch)
    pred_before = np.asarray(t.predict(batch))
    t.save(str(tmp_path / "ckpt"))

    t2 = Trainer("resnet50", config=config, mesh_config=MeshConfig(dp=8),
                 seed=99)
    t2.restore(str(tmp_path / "ckpt"))
    np.testing.assert_allclose(
        np.asarray(t2.predict(batch)), pred_before, rtol=1e-5
    )


@pytest.mark.parametrize("table_update", ["dense", "sparse"])
def test_widedeep_embedding_step(table_update):
    """Trainer picks up widedeep's custom step in both table-update modes:
    tables live in the 'embedding' collection (NOT the optax param tree),
    only the gathered rows change per step (bit-wise, in both modes), and
    the MLP trains through the optax optimizer (AdamW default / explicit
    override respected)."""
    import dataclasses

    import jax
    import optax

    from tensorflowonspark_tpu.models import widedeep
    from tensorflowonspark_tpu.parallel.mesh import MeshConfig
    from tensorflowonspark_tpu.trainer import Trainer

    t = Trainer(
        "wide_deep",
        config=dataclasses.replace(widedeep.Config.tiny(),
                                   table_update=table_update),
        mesh_config=MeshConfig(dp=2, fsdp=2, tp=2),
    )
    # tables are out of the param/optax tree entirely
    assert set(t.state.collections) == {"embedding", "embedding_opt"}
    assert not any("embedding" in str(p) for p, _ in
                   jax.tree_util.tree_flatten_with_path(t.state.params)[0])

    cfg = widedeep.Config.tiny()
    before = np.asarray(t.state.collections["embedding"]["deep"])
    batch = widedeep.example_batch(cfg, batch_size=16)
    losses = [float(t.step(batch)) for _ in range(6)]
    assert losses[-1] < losses[0]

    # sparseness contract: rows never gathered are bit-identical
    after = np.asarray(t.state.collections["embedding"]["deep"])
    ids = np.asarray(widedeep.fold_ids(
        jax.numpy.asarray(batch["cat"]), cfg)).reshape(-1)
    untouched = np.setdiff1d(np.arange(cfg.total_buckets), ids)
    assert untouched.size > 0
    np.testing.assert_array_equal(after[untouched], before[untouched])
    assert not np.array_equal(after[ids[0]], before[ids[0]])
    # touched rows accumulated AdaGrad state
    acc = np.asarray(t.state.collections["embedding_opt"]["deep_acc"])
    assert (acc[ids] > 0).any() and (acc[untouched] == 0).all()

    explicit = optax.sgd(0.1)
    t2 = Trainer("wide_deep", optimizer=explicit, mesh_config=MeshConfig(dp=8))
    assert t2.optimizer is explicit


# -- the default table update's two executions (PR 30) ------------------------


def _table_state(trainer) -> dict:
    cols = trainer.state.collections
    return {k: np.asarray(v)
            for k, v in {**cols["embedding"], **cols["embedding_opt"]}.items()}


def _table_step_counts() -> tuple:
    from tensorflowonspark_tpu import obs

    return (obs.counter("table_update_rows_steps_total").value,
            obs.counter("table_update_full_steps_total").value)


def _cat_batch(kind, cfg, batch_size=16):
    """A batch whose ids have no duplicates in a column, are one id, or
    follow a Zipf law (a few hot ids and a tail)."""
    from tensorflowonspark_tpu.models import widedeep

    rng = np.random.RandomState(5)
    batch = widedeep.example_batch(cfg, batch_size=batch_size)
    if kind == "no_duplicates":
        cat = np.stack([rng.permutation(cfg.hash_buckets)[:batch_size]
                        for _ in range(widedeep.NUM_CAT)], axis=1)
    elif kind == "one_id":
        cat = np.full((batch_size, widedeep.NUM_CAT), 7)
    else:
        cat = np.minimum(rng.zipf(1.3, (batch_size, widedeep.NUM_CAT)) - 1,
                         cfg.hash_buckets - 1)
    batch["cat"] = cat.astype(np.int32)
    return batch


def test_widedeep_default_is_dense_and_sparse_is_still_accepted():
    """``"dense"`` and ``"sparse"`` name the two AdaGrad variants; how the
    default executes is no option (the benchmark's configuration pins the
    string and refuses another default)."""
    import dataclasses

    from tensorflowonspark_tpu.models import widedeep

    assert widedeep.Config().table_update == "dense"
    assert [f.name for f in dataclasses.fields(widedeep.Config)] == [
        "hash_buckets", "embed_dim", "hidden", "dtype", "table_dtype",
        "table_lr", "table_update"]
    Trainer("wide_deep", config=dataclasses.replace(
        widedeep.Config.tiny(), table_update="sparse"),
        mesh_config=MeshConfig(dp=8))
    with pytest.raises(ValueError, match="dense|sparse"):
        Trainer("wide_deep", config=dataclasses.replace(
            widedeep.Config.tiny(), table_update="rows"),
            mesh_config=MeshConfig(dp=8))


@pytest.mark.parametrize("rows,ids,touched", [
    (16_900_000, 26_624, True),    # the benchmark's cell: 635 rows an id
    (2_600_000, 106_496, False),   # the program's defaults: 24
    (1_300, 416, False),           # the tiny config of these tests: 3
    (26_624 * 1_000, 26_624, True),
    (26_624, 26_624, False),
])
def test_widedeep_update_rule_is_a_function_of_static_shapes(rows, ids,
                                                             touched):
    from tensorflowonspark_tpu.models import widedeep

    assert widedeep.update_touches_rows(rows, ids) is touched
    # both sides of the threshold itself
    at = widedeep.ROWS_PER_ID_CROSSOVER * ids
    assert widedeep.update_touches_rows(at, ids) is True
    assert widedeep.update_touches_rows(at - 1, ids) is False


@pytest.mark.parametrize("kind", ["no_duplicates", "one_id", "zipf"])
def test_widedeep_rows_pass_is_the_full_pass(kind, monkeypatch):
    """The same state and batch through both executions of the default
    update: losses, tables and accumulators agree to float32 rounding and
    the rows the batch never looked up are bit-identical to the start."""
    from tensorflowonspark_tpu.models import widedeep

    cfg = widedeep.Config.tiny()
    batch = _cat_batch(kind, cfg)
    runs = {}
    for name, crossover in (("full", 10 ** 9), ("rows", 0)):
        monkeypatch.setattr(widedeep, "ROWS_PER_ID_CROSSOVER", crossover)
        t = Trainer("wide_deep", config=cfg, mesh_config=MeshConfig(dp=8),
                    seed=3)
        start = _table_state(t)
        before = _table_step_counts()
        losses = [float(t.step(batch)) for _ in range(3)]
        after = _table_step_counts()
        assert (after[0] - before[0], after[1] - before[1]) == (
            (3, 0) if name == "rows" else (0, 3))
        runs[name] = (losses, _table_state(t))
    np.testing.assert_allclose(runs["rows"][0], runs["full"][0], rtol=1e-6)
    ids = np.asarray(widedeep.fold_ids(batch["cat"], cfg)).reshape(-1)
    untouched = np.setdiff1d(np.arange(cfg.total_buckets), ids)
    for key, full in runs["full"][1].items():
        rows = runs["rows"][1][key]
        np.testing.assert_allclose(rows, full, rtol=1e-5, atol=1e-7)
        np.testing.assert_array_equal(rows[untouched], start[key][untouched])
        assert not np.array_equal(rows[ids[0]], start[key][ids[0]])


def test_widedeep_rows_pass_vocab_sharded_matches_replicated():
    """A table large enough for its batch takes the touched-rows pass by
    the shape rule alone; on ``dp=2, tp=4`` with vocab-sharded tables it
    gives the replicated run's losses and tables."""
    import dataclasses

    from tensorflowonspark_tpu.models import widedeep

    cfg = dataclasses.replace(widedeep.Config.tiny(), hash_buckets=8_000)
    batch = _cat_batch("zipf", cfg, batch_size=8)
    ids_a_step = batch["cat"].size
    assert widedeep.update_touches_rows(cfg.total_buckets // 4, ids_a_step)

    before = _table_step_counts()
    t_tp = Trainer("wide_deep", config=cfg,
                   mesh_config=MeshConfig(dp=2, tp=4), seed=3)
    assert t_tp.state.collections["embedding"]["deep"].sharding.spec[0] == "tp"
    t_rep = Trainer("wide_deep", config=cfg, mesh_config=MeshConfig(dp=8),
                    seed=3)
    for _ in range(4):
        np.testing.assert_allclose(float(t_tp.step(batch)),
                                   float(t_rep.step(batch)), rtol=1e-5)
    after = _table_step_counts()
    assert (after[0] - before[0], after[1] - before[1]) == (8, 0)
    rep = _table_state(t_rep)
    for key, tp in _table_state(t_tp).items():
        np.testing.assert_allclose(tp, rep[key], rtol=1e-5, atol=1e-7)
    assert t_tp.state.collections["embedding"]["deep"].sharding.spec[0] == "tp"


def test_widedeep_rows_step_holds_no_table_shaped_temporary():
    """The compiled touched-rows step allocates nothing of a table's shape
    (the full pass holds the dense gradient: a whole table of scratch)."""
    import dataclasses

    import jax

    from tensorflowonspark_tpu.models import widedeep

    cfg = dataclasses.replace(widedeep.Config.tiny(), hash_buckets=8_000)
    table_bytes = cfg.total_buckets * cfg.embed_dim * 4
    t = Trainer("wide_deep", config=cfg, devices=jax.devices()[:1])
    temp = {}
    for batch_size in (8, 2_048):  # 1,000 and 4 table rows an id
        staged = t.shard(_cat_batch("zipf", cfg, batch_size=batch_size))
        compiled = t.train_step.lower(t.state, staged).compile()
        temp[batch_size] = compiled.memory_analysis().temp_size_in_bytes
    assert widedeep.update_touches_rows(cfg.total_buckets, 8 * 26)
    assert not widedeep.update_touches_rows(cfg.total_buckets, 2_048 * 26)
    assert temp[8] < table_bytes / 4, temp
    assert temp[2_048] >= table_bytes, temp


def test_widedeep_rows_step_names_its_table_lookups_forward():
    """The touched-rows step takes the gradient w.r.t. the gathered rows,
    so its table lookups sit outside the differentiated function and carry
    no ``jvp(``; the ``forward`` scope keeps them in the forward pass for a
    profile's reader (the benchmark's ``device_forward_ms``), as the full
    pass's lookups are.  The accumulators' lookups are the optimizer's."""
    import dataclasses
    import re

    import jax

    from tensorflowonspark_tpu.models import widedeep

    cfg = dataclasses.replace(widedeep.Config.tiny(), hash_buckets=8_000)
    t = Trainer("wide_deep", config=cfg, devices=jax.devices()[:1])
    staged = t.shard(_cat_batch("zipf", cfg, batch_size=8))
    hlo = t.train_step.lower(t.state, staged).compile().as_text()
    lookups = re.findall(r' gather\(.*op_name="([^"]*)"', hlo)
    forward = [n for n in lookups if re.search(r"(^|/)forward(/|$)", n)]
    assert len(lookups) == 4 and len(forward) == 2, lookups
    assert not any("jvp(" in n for n in lookups)


def _crossover_tool():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "table_update_crossover.py")
    spec = importlib.util.spec_from_file_location("crossover_tool", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def test_widedeep_crossover_constant_is_what_its_measurements_fit():
    """The four chip measurements written beside ROWS_PER_ID_CROSSOVER,
    through the tool's own fit: the constant lies where the two executions'
    fitted costs meet."""
    from tensorflowonspark_tpu.models import widedeep

    measured = [  # buckets, batch, rows pass ms, full pass ms (PR 30)
        (650_000, 1_024, 9.97, 24.70), (650_000, 4_096, 40.19, 39.43),
        (100_000, 1_024, 9.63, 7.92), (100_000, 4_096, 39.71, 21.80)]
    got = _crossover_tool().fit([
        {"buckets": b, "batch": n, "rows_ms": r, "full_ms": f}
        for b, n, r, f in measured])
    assert 1.1 < got["full_ns_a_table_row"] < 1.3
    assert 350 < got["rows_ns_an_id"] < 400
    assert abs(got["rows_per_id_crossover"]
               - widedeep.ROWS_PER_ID_CROSSOVER) < 10


def test_widedeep_crossover_tool_forces_each_execution(capsys):
    """``tools/table_update_crossover.py`` at a toy size: each side's run
    takes that side (the counters say), the constant is put back, and a
    line a shape and the fit come out."""
    import json

    from tensorflowonspark_tpu.models import widedeep

    tool = _crossover_tool()
    before = _table_step_counts()
    assert tool.main(["--shapes", "50x8,400x8,50x32", "--steps", "2",
                      "--repeats", "1"]) == 0
    after = _table_step_counts()
    # a shape and a side: 3 warm steps + 1 repeat of 2
    assert (after[0] - before[0], after[1] - before[1]) == (15, 15)
    assert widedeep.ROWS_PER_ID_CROSSOVER == 160
    lines = [json.loads(line)
             for line in capsys.readouterr().out.splitlines()[1:]]
    assert [(r["buckets"], r["batch"], r["rule_takes"])
            for r in lines[:3]] == [(50, 8, "full"), (400, 8, "full"),
                                    (50, 32, "full")]
    assert all(r["rows_ms"] > 0 and r["full_ms"] > 0 for r in lines[:3])
    assert "rows_per_id_crossover" in lines[3]


def test_bert_pipeline_parallel_matches_sequential():
    """config.pp_stages > 1: the stacked GPipe trunk on a pp mesh produces
    the same forward as the identical params run sequentially (pp=1 mesh),
    and trains to decreasing loss."""
    import dataclasses

    from tensorflowonspark_tpu.models import bert

    cfg = dataclasses.replace(bert.Config.tiny(), pp_stages=2,
                              pp_microbatches=2)
    batch = bert.example_batch(cfg, batch_size=8, seq_len=16)

    t_pp = Trainer("bert", config=cfg, mesh_config=MeshConfig(pp=2, dp=4),
                   seed=7)
    t_seq = Trainer("bert", config=cfg, mesh_config=MeshConfig(dp=8), seed=7)

    s_pp, e_pp = t_pp.predict(batch)
    s_sq, e_sq = t_seq.predict(batch)
    np.testing.assert_allclose(np.asarray(s_pp), np.asarray(s_sq),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(e_pp), np.asarray(e_sq),
                               rtol=2e-4, atol=2e-4)

    losses = [float(t_pp.step(batch)) for _ in range(4)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_bert_pp_composes_with_tp_and_fsdp():
    """VERDICT r3 item 3: the pipelined trunk on a {dp, pp:2, tp:2} mesh —
    stage-internal Megatron tp (head/ffn sharding + psum) inside the GPipe
    schedule — must match the sequential single-strategy run, and train.
    Also proves pp×fsdp (ZeRO storage sharding under the pipeline)."""
    import dataclasses

    from tensorflowonspark_tpu.models import bert

    cfg = dataclasses.replace(bert.Config.tiny(), pp_stages=2,
                              pp_microbatches=2)
    batch = bert.example_batch(cfg, batch_size=8, seq_len=16)

    t_ref = Trainer("bert", config=cfg, mesh_config=MeshConfig(dp=8), seed=3)
    for mc in (MeshConfig(dp=2, pp=2, tp=2),
               MeshConfig(dp=1, fsdp=2, pp=2, tp=2)):
        t = Trainer("bert", config=cfg, mesh_config=mc, seed=3)
        s, e = t.predict(batch)
        s_r, e_r = t_ref.predict(batch)
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_r),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(e), np.asarray(e_r),
                                   rtol=2e-4, atol=2e-4)
        losses = [float(t.step(batch)) for _ in range(3)]
        assert np.isfinite(losses).all() and losses[-1] < losses[0], (mc,
                                                                      losses)


def test_mobilenet_published_shapes_and_width_mult():
    """MobileNetV1 at full size: 224 input runs the published stride
    schedule down to a 7×7×1024 feature map before the pool (abstract
    eval — no FLOPs); the width multiplier scales channels in multiples
    of 8."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import mobilenet
    from tensorflowonspark_tpu.parallel.train import unbox

    cfg = mobilenet.Config()  # width 1.0, 224, 1000 classes
    module = mobilenet.make_model(cfg)
    x = jax.ShapeDtypeStruct((2, 224, 224, 3), jnp.float32)
    var_shapes = jax.eval_shape(
        lambda v: module.init(jax.random.PRNGKey(0), v), x)
    params = unbox(var_shapes)["params"]
    # last pointwise conv carries the 7x7 stage's 1024 channels
    assert params["pw_12"]["kernel"].shape == (1, 1, 1024, 1024)
    # depthwise kernels are one filter per channel (feature_group_count)
    assert params["dw_12"]["kernel"].shape[-2] == 1
    out = jax.eval_shape(
        lambda p, v: module.apply({"params": p}, v), params, x)
    assert out.shape == (2, 1000)

    assert mobilenet._scaled(1024, 0.25) == 256
    assert mobilenet._scaled(32, 0.25) == 8
    assert mobilenet._scaled(64, 0.1) == 8  # floor


def test_inception_canonical_stem_shapes():
    """VERDICT r4 missing #3: Config(canonical=True) is the PUBLISHED
    Inception-v3 — VALID stem 299→149→147→147→73→71→35, reductions
    35→17→8.  The shape pins are trace-time asserts inside the model;
    abstract-evaluating the full 299 forward exercises every one for free
    (no FLOPs)."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import inception
    from tensorflowonspark_tpu.parallel.train import unbox

    cfg = inception.Config(canonical=True)  # full size, abstract only
    module = inception.make_model(cfg)
    x = jax.ShapeDtypeStruct((2, 299, 299, 3), jnp.float32)
    var_shapes = jax.eval_shape(
        lambda v: module.init(jax.random.PRNGKey(0), v), x)
    params = unbox(var_shapes)["params"]
    assert "aux" in params, sorted(params)  # aux head params exist at init
    # train=True returns (logits, aux_logits), both (B, classes)
    out = jax.eval_shape(
        lambda p, v: module.apply({"params": p}, v, train=True), params, x)
    assert out[0].shape == (2, 1000) and out[1].shape == (2, 1000)
    # inference: main logits only (aux is train-time regularization)
    out_infer = jax.eval_shape(
        lambda p, v: module.apply({"params": p}, v), params, x)
    assert out_infer.shape == (2, 1000)


@pytest.mark.slow  # ~88 s: aux-head compile; stem-shape coverage stays fast
def test_inception_canonical_trains():
    """The canonical tiny config trains with the aux-weighted loss and
    serves a single-logits forward through the Trainer path."""
    from tensorflowonspark_tpu.models import inception

    cfg = inception.Config.tiny_canonical()
    t = Trainer("inception_v3", config=cfg, mesh_config=MeshConfig(dp=8),
                learning_rate=1e-2)
    batch = inception.example_batch(cfg, batch_size=8)
    losses = [float(t.step(batch)) for _ in range(3)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
    logits = t.predict(batch)
    assert np.asarray(logits).shape == (8, cfg.num_classes)


def test_bert_pp_composes_with_sp_ring_attention():
    """VERDICT r4 item 5: ring attention INSIDE pipeline stages — the sp
    axis stays free inside the GPipe shard_map, K/V blocks ppermute around
    the ring per stage, and {pp:2, sp:2} matches the sequential dp-only
    run.  Also proves the full pp×tp×sp stack on one mesh."""
    import dataclasses

    from tensorflowonspark_tpu.models import bert

    cfg = dataclasses.replace(bert.Config.tiny(), pp_stages=2,
                              pp_microbatches=2)
    batch = bert.example_batch(cfg, batch_size=8, seq_len=16)
    # padding in the ring path must behave identically too; span labels
    # stay on VISIBLE positions (a label on a masked -1e30 logit makes the
    # loss astronomically large by construction, on any mesh)
    batch["attention_mask"][:, 12:] = 0
    batch["start_positions"] = batch["start_positions"] % 12
    batch["end_positions"] = batch["end_positions"] % 12

    t_ref = Trainer("bert", config=cfg, mesh_config=MeshConfig(dp=8), seed=5)
    s_r, e_r = t_ref.predict(batch)
    for mc in (MeshConfig(dp=2, pp=2, sp=2),
               MeshConfig(dp=1, pp=2, tp=2, sp=2)):
        t = Trainer("bert", config=cfg, mesh_config=mc, seed=5)
        s, e = t.predict(batch)
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_r),
                                   rtol=2e-4, atol=2e-4, err_msg=str(mc))
        np.testing.assert_allclose(np.asarray(e), np.asarray(e_r),
                                   rtol=2e-4, atol=2e-4, err_msg=str(mc))
        losses = [float(t.step(batch)) for _ in range(3)]
        assert np.isfinite(losses).all() and losses[-1] < losses[0], (mc,
                                                                      losses)


def test_bert_layered_sp_impl_selectable():
    """Config(sp_impl=...) picks the sequence-parallel kernel on the
    layered path: ulysses (all_to_all head re-shard) must match the dense
    dp-only run like ring does; inside the GPipe trunk ulysses is a clean
    construction-time error (all_to_all does not lower in the nested
    scan)."""
    import dataclasses

    import pytest as _pytest

    from tensorflowonspark_tpu.models import bert
    from tensorflowonspark_tpu.parallel import build_mesh

    cfg = dataclasses.replace(bert.Config.tiny(), sp_impl="ulysses")
    batch = bert.example_batch(cfg, batch_size=8, seq_len=16)
    t_ref = Trainer("bert", config=bert.Config.tiny(),
                    mesh_config=MeshConfig(dp=8), seed=2)
    t_u = Trainer("bert", config=cfg, mesh_config=MeshConfig(dp=2, sp=4),
                  seed=2)
    s_u, e_u = t_u.predict(batch)
    s_r, e_r = t_ref.predict(batch)
    np.testing.assert_allclose(np.asarray(s_u), np.asarray(s_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(e_u), np.asarray(e_r),
                               rtol=2e-4, atol=2e-4)

    with _pytest.raises(ValueError, match="unsupported inside the GPipe"):
        bert.make_model(
            dataclasses.replace(bert.Config.tiny(), pp_stages=2,
                                sp_impl="ulysses"),
            mesh=build_mesh(MeshConfig(pp=2, sp=2, dp=2)))
    with _pytest.raises(ValueError, match="ring' or 'ulysses"):
        bert.make_model(dataclasses.replace(bert.Config.tiny(),
                                            sp_impl="flash"))


def test_bert_pp_tp_divisibility_validation():
    import dataclasses

    import pytest as _pytest

    from tensorflowonspark_tpu.models import bert
    from tensorflowonspark_tpu.parallel import build_mesh

    mesh = build_mesh(MeshConfig(dp=1, pp=2, tp=4))
    cfg = dataclasses.replace(bert.Config.tiny(), heads=2, pp_stages=2)
    with _pytest.raises(ValueError, match="divisible by tp"):
        bert.make_model(cfg, mesh=mesh)


def test_bert_pp_config_validation():
    import dataclasses

    import pytest as _pytest

    from tensorflowonspark_tpu.models import bert
    from tensorflowonspark_tpu.parallel import build_mesh

    with _pytest.raises(ValueError, match="not divisible"):
        bert.make_model(dataclasses.replace(bert.Config.tiny(), pp_stages=3))
    # pp×sp is a SUPPORTED composition since round 5 (ring attention inside
    # pipeline stages) — construction must succeed
    mesh = build_mesh(MeshConfig(pp=2, sp=2, dp=2))
    bert.make_model(
        dataclasses.replace(bert.Config.tiny(), pp_stages=2), mesh=mesh)


def test_bert_stacked_encoder_matches_layered_block():
    """The StackedEncoder's hand-rolled block math must match the layered
    flax Block bit-for-tolerance: map the layered params onto the stacked
    layout and compare forwards. Pins the two implementations together so
    a change to one (eps, masking value, dtype policy) fails loudly
    instead of silently diverging the pp variant."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from flax.linen import meta

    from tensorflowonspark_tpu.models import bert

    cfg = bert.Config.tiny()  # layers=2, dtype float32
    cfg_pp = dataclasses.replace(cfg, pp_stages=2, pp_microbatches=2)
    batch = bert.example_batch(cfg, batch_size=4, seq_len=16)

    layered = bert.make_model(cfg)
    stacked = bert.make_model(cfg_pp)
    lp = meta.unbox(layered.init(
        jax.random.PRNGKey(0), batch["input_ids"], batch["token_type_ids"],
        batch["attention_mask"]))["params"]
    sp = meta.unbox(stacked.init(
        jax.random.PRNGKey(0), batch["input_ids"], batch["token_type_ids"],
        batch["attention_mask"]))["params"]

    # graft the layered weights into the stacked (head-major) layout
    H, nh, hd = cfg.hidden, cfg.heads, cfg.head_dim
    enc = dict(sp["encoder"])
    for i in range(cfg.layers):
        layer = lp[f"layer_{i}"]
        att = layer["attention"]
        enc["qkv_w"] = enc["qkv_w"].at[i].set(att["qkv"]["kernel"])
        enc["qkv_b"] = enc["qkv_b"].at[i].set(att["qkv"]["bias"])
        enc["out_w"] = enc["out_w"].at[i].set(
            att["out"]["kernel"].reshape(nh, hd, H))
        enc["out_b"] = enc["out_b"].at[i].set(att["out"]["bias"])
        enc["ln1_s"] = enc["ln1_s"].at[i].set(layer["ln_attn"]["scale"])
        enc["ln1_b"] = enc["ln1_b"].at[i].set(layer["ln_attn"]["bias"])
        enc["mlp_in_w"] = enc["mlp_in_w"].at[i].set(
            layer["mlp_in"]["kernel"])
        enc["mlp_in_b"] = enc["mlp_in_b"].at[i].set(layer["mlp_in"]["bias"])
        enc["mlp_out_w"] = enc["mlp_out_w"].at[i].set(
            layer["mlp_out"]["kernel"])
        enc["mlp_out_b"] = enc["mlp_out_b"].at[i].set(
            layer["mlp_out"]["bias"])
        enc["ln2_s"] = enc["ln2_s"].at[i].set(layer["ln_mlp"]["scale"])
        enc["ln2_b"] = enc["ln2_b"].at[i].set(layer["ln_mlp"]["bias"])
    grafted = {**sp, "encoder": enc,
               "embeddings": lp["embeddings"], "span": lp["span"]}

    args = (batch["input_ids"], batch["token_type_ids"],
            batch["attention_mask"])
    s_l, e_l = layered.apply({"params": lp}, *args)
    s_s, e_s = stacked.apply({"params": grafted}, *args)
    np.testing.assert_allclose(np.asarray(s_s), np.asarray(s_l),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(e_s), np.asarray(e_l),
                               rtol=1e-4, atol=1e-4)


def test_widedeep_rejects_half_pregathered_call():
    """emb_rows without wide_rows used to crash with an opaque
    AttributeError deep in the forward (ADVICE r3); now a ValueError up
    front names the contract."""
    import jax
    import pytest as _pytest

    from flax.linen import meta

    from tensorflowonspark_tpu.models import widedeep

    cfg = widedeep.Config.tiny()
    module = widedeep.make_model(cfg)
    batch = widedeep.example_batch(cfg, batch_size=2)
    variables = meta.unbox(
        module.init(jax.random.PRNGKey(0), batch["dense"], batch["cat"]))
    emb_rows = np.zeros((2, widedeep.NUM_CAT, cfg.embed_dim), np.float32)
    with _pytest.raises(ValueError, match="emb_rows and wide_rows"):
        module.apply(
            {"params": variables["params"],
             "embedding": variables["embedding"]},
            batch["dense"], batch["cat"], emb_rows=emb_rows)


@pytest.mark.parametrize("table_update", ["dense", "sparse"])
def test_widedeep_vocab_sharded_tables(table_update):
    """tp > 1: the embedding tables and accumulators materialize
    vocab-sharded over tp (capacity: 1/tp of the table per device) and the
    training numerics match the fully-replicated run."""
    import dataclasses

    import jax

    from tensorflowonspark_tpu.models import widedeep
    from tensorflowonspark_tpu.parallel.mesh import MeshConfig
    from tensorflowonspark_tpu.trainer import Trainer

    cfg = dataclasses.replace(widedeep.Config.tiny(),
                              table_update=table_update)
    batch = widedeep.example_batch(cfg, batch_size=16)

    t_tp = Trainer("wide_deep", config=cfg,
                   mesh_config=MeshConfig(dp=2, tp=4), seed=3)
    deep = t_tp.state.collections["embedding"]["deep"]
    acc = t_tp.state.collections["embedding_opt"]["deep_acc"]
    assert deep.sharding.spec[0] == "tp", deep.sharding
    assert acc.sharding.spec[0] == "tp", acc.sharding
    # each device holds 1/tp of the vocab rows
    shard_rows = {s.data.shape[0] for s in deep.addressable_shards}
    assert shard_rows == {cfg.total_buckets // 4}

    t_rep = Trainer("wide_deep", config=cfg, mesh_config=MeshConfig(dp=8),
                    seed=3)
    for _ in range(4):
        l_tp = float(t_tp.step(batch))
        l_rep = float(t_rep.step(batch))
        np.testing.assert_allclose(l_tp, l_rep, rtol=1e-4)
    # sharding survives the step (donated buffers updated in place)
    assert t_tp.state.collections["embedding"]["deep"].sharding.spec[0] == "tp"
