"""Bucketed, overlapped gradient collectives (``parallel/collectives.py``):
partitioner units, bucketed-vs-monolithic numerical equivalence across mesh
layouts, opt-outs, and the trainer/elastic composition — on the virtual
8-device CPU mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.parallel import (
    MeshConfig,
    apply_zero_sharding,
    build_mesh,
    collectives,
    create_train_state,
    ideal_serial_allreduce_seconds,
    infer_param_sharding,
    make_bucketed_train_step,
    make_train_step,
    partition_buckets,
    shard_batch,
)

TOL = dict(rtol=5e-5, atol=1e-7)  # the test_parallel f32 tolerances


class _Leaf:
    """Fake leaf with a size/dtype for partitioner units (no device)."""

    def __init__(self, nbytes):
        self.size = nbytes // 4
        self.dtype = np.dtype(np.float32)


# -- partitioner units --------------------------------------------------------


def test_partition_oversize_leaf_stands_alone():
    kb = 1024
    leaves = [_Leaf(2 * kb), _Leaf(100 * kb), _Leaf(2 * kb)]
    buckets = partition_buckets(leaves, bucket_bytes=10 * kb)
    assert buckets == [[0], [1], [2]]
    # oversize leaves are never split, even back to back
    buckets = partition_buckets([_Leaf(100 * kb), _Leaf(100 * kb)],
                                bucket_bytes=10 * kb)
    assert buckets == [[0], [1]]


def test_partition_coalesces_small_leaves():
    kb = 1024
    leaves = [_Leaf(3 * kb)] * 5
    buckets = partition_buckets(leaves, bucket_bytes=10 * kb)
    assert buckets == [[0, 1, 2], [3, 4]]
    # an oversize leaf mid-stream flushes the open bucket
    leaves = [_Leaf(3 * kb), _Leaf(100 * kb), _Leaf(3 * kb), _Leaf(3 * kb)]
    assert partition_buckets(leaves, 10 * kb) == [[0], [1], [2, 3]]


def test_partition_deterministic_and_total():
    rng = np.random.RandomState(0)
    leaves = [_Leaf(int(rng.randint(1, 64)) * 1024) for _ in range(40)]
    a = partition_buckets(leaves, 64 * 1024)
    b = partition_buckets(leaves, 64 * 1024)
    assert a == b  # pure function of order + sizes
    flat = [i for bucket in a for i in bucket]
    assert flat == list(range(len(leaves)))  # total, in flatten order


def test_bucket_bytes_default_env_override(monkeypatch):
    monkeypatch.setenv("TFOS_ALLREDUCE_BUCKET_MB", "2.5")
    assert collectives.bucket_bytes_default() == int(2.5 * 1024 * 1024)
    monkeypatch.setenv("TFOS_ALLREDUCE_BUCKET_MB", "garbage")
    assert collectives.bucket_bytes_default() == int(
        collectives.DEFAULT_BUCKET_MB * 1024 * 1024)


# -- eligibility / opt-out ----------------------------------------------------


def test_model_parallel_meshes_keep_monolithic_step():
    for mc, axis in ((MeshConfig(dp=4, tp=2), "tp"),
                     (MeshConfig(dp=4, sp=2), "sp"),
                     (MeshConfig(dp=4, pp=2), "pp"),
                     (MeshConfig(dp=4, ep=2), "ep")):
        ok, reason = collectives.mesh_eligibility(build_mesh(mc))
        assert not ok and axis in reason, (mc, reason)
    ok, reason = collectives.mesh_eligibility(build_mesh(MeshConfig(dp=8)))
    assert ok
    ok, reason = collectives.mesh_eligibility(
        build_mesh(MeshConfig(dp=2, fsdp=4)))
    assert ok


def test_env_opt_out_and_force(monkeypatch):
    mesh = build_mesh(MeshConfig(dp=8))
    state, opt, shardings, loss_fn, batch = _toy_setup(mesh)
    monkeypatch.setenv("TFOS_BUCKETED_ALLREDUCE", "0")
    step = make_train_step(loss_fn, opt, mesh, shardings, state, batch)
    assert step.bucketed is False
    monkeypatch.delenv("TFOS_BUCKETED_ALLREDUCE")
    step = make_train_step(loss_fn, opt, mesh, shardings, state, batch)
    assert step.bucketed is True and step.n_buckets >= 1
    # forcing bucketed on an ineligible mesh names the reason
    mesh_tp = build_mesh(MeshConfig(dp=4, tp=2))
    state, opt, shardings, loss_fn, batch = _toy_setup(mesh_tp)
    with pytest.raises(ValueError, match="tp"):
        make_train_step(loss_fn, opt, mesh_tp, shardings, state, batch,
                        bucketed=True)


def test_single_data_shard_keeps_monolithic_step():
    mesh = build_mesh(MeshConfig(dp=1, tp=1), devices=jax.devices()[:1])
    ok, reason = collectives.mesh_eligibility(mesh)
    assert not ok and "single data shard" in reason


# -- numerical equivalence ----------------------------------------------------


def _toy_setup(mesh, zero=False, stateful=False, n_leaves=6):
    """Toy multi-leaf model so the bucket partitioner has real work."""
    import optax

    rng = np.random.RandomState(0)
    params = {"emb": jnp.asarray(rng.randn(16, 8) * 0.1, jnp.float32)}
    for i in range(n_leaves - 2):
        params[f"w{i}"] = jnp.asarray(rng.randn(8, 8) * 0.3, jnp.float32)
    params["head"] = jnp.asarray(rng.randn(8, 4) * 0.3, jnp.float32)
    optimizer = optax.adamw(5e-2)
    cols = ({"stats": {"mean": jnp.zeros((8,), jnp.float32),
                       "count": jnp.zeros((), jnp.int32)}}
            if stateful else None)
    state = create_train_state(params, optimizer, cols)
    shardings = infer_param_sharding(params, mesh, min_dim=1)
    if zero:
        shardings = apply_zero_sharding(shardings, mesh, params, min_size=1)

    n_body = n_leaves - 2

    if stateful:
        # BatchNorm-style stateful loss: normalization reads the RUNNING
        # statistics collection, whose update is the batch mean of the
        # activations — the linear statistic the bucketed step's
        # cross-replica pmean reproduces exactly
        def loss_fn(p, c, batch):
            h = p["emb"][batch["ids"]]
            for i in range(n_body):
                h = jnp.tanh(h @ p[f"w{i}"])
            h = h - c["stats"]["mean"]
            pred = h @ p["head"]
            new = {"stats": {
                "mean": 0.9 * c["stats"]["mean"]
                + 0.1 * jnp.mean(h, axis=0),
                "count": c["stats"]["count"] + 1}}
            return jnp.mean((pred - batch["y"]) ** 2), new

        loss_fn.stateful = True
    else:
        def loss_fn(p, batch):
            h = p["emb"][batch["ids"]]
            for i in range(n_body):
                h = jnp.tanh(h @ p[f"w{i}"])
            pred = h @ p["head"]
            return jnp.mean((pred - batch["y"]) ** 2)

    batch = {"ids": rng.randint(0, 16, (16,)).astype(np.int32),
             "y": rng.randn(16, 4).astype(np.float32)}
    return state, optimizer, shardings, loss_fn, batch


def _assert_steps_match(mesh, zero=False, stateful=False, steps=5,
                        bucket_bytes=200):
    state_m, opt, shardings, loss_fn, batch = _toy_setup(
        mesh, zero=zero, stateful=stateful)
    state_b, *_ = _toy_setup(mesh, zero=zero, stateful=stateful)
    mono = make_train_step(loss_fn, opt, mesh, shardings, state_m, batch,
                           bucketed=False)
    buck = make_bucketed_train_step(loss_fn, opt, mesh, shardings, state_b,
                                    batch, bucket_bytes=bucket_bytes)
    assert buck.bucketed and buck.n_buckets > 1  # a real multi-bucket plan
    sharded = shard_batch(mesh, batch)
    for _ in range(steps):
        state_m, loss_m = mono(state_m, sharded)
        state_b, loss_b = buck(state_b, sharded)
        np.testing.assert_allclose(float(loss_m), float(loss_b), **TOL)
    for key in state_m.params:
        np.testing.assert_allclose(np.asarray(state_m.params[key]),
                                   np.asarray(state_b.params[key]),
                                   err_msg=key, **TOL)
    if stateful:
        np.testing.assert_allclose(
            np.asarray(state_m.collections["stats"]["mean"]),
            np.asarray(state_b.collections["stats"]["mean"]), **TOL)
        assert int(state_b.collections["stats"]["count"]) == steps
    return state_b


def test_bucketed_matches_monolithic_dp_only():
    _assert_steps_match(build_mesh(MeshConfig(dp=8)))


def test_bucketed_matches_monolithic_dp_fsdp_zero():
    state = _assert_steps_match(build_mesh(MeshConfig(dp=2, fsdp=4)),
                                zero=True)
    # ZeRO storage sharding survives the bucketed step
    assert any(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda p: "fsdp" in str(p.sharding.spec), state.params)))


def test_bucketed_matches_monolithic_stateful_batchnorm():
    _assert_steps_match(build_mesh(MeshConfig(dp=8)), stateful=True)


def test_bucketed_matches_monolithic_stateful_zero():
    _assert_steps_match(build_mesh(MeshConfig(dp=4, fsdp=2)), zero=True,
                        stateful=True)


def _asked_allreduces(step, state, mesh, batch) -> int:
    """All-reduce ops in the module the step LOWERS to — the structure the
    program asks for.  The compiled module no longer shows it: the
    installed XLA combines the per-bucket all-reduces into one."""
    lowered = step.lower(state, shard_batch(mesh, batch))
    return lowered.as_text().count("stablehlo.all_reduce")


def test_bucketed_step_emits_one_collective_per_bucket():
    """The PR 12 structural claim (all-reduce structure, pinned via
    ``update_shard=False``): the lowered HLO carries one explicit
    all-reduce per gradient bucket (plus the scalar loss pmean), instead
    of whatever the GSPMD combiner felt like."""
    mesh = build_mesh(MeshConfig(dp=8))
    state, opt, shardings, loss_fn, batch = _toy_setup(mesh)
    buck = make_bucketed_train_step(loss_fn, opt, mesh, shardings, state,
                                    batch, bucket_bytes=200,
                                    update_shard=False)
    n_allreduce = _asked_allreduces(buck, state, mesh, batch)
    assert n_allreduce == buck.n_buckets + 1, (n_allreduce, buck.n_buckets)


def test_no_reduce_twin_diverges():
    """The bench's compute-only twin must really skip the gradient
    exchange (otherwise the exposed-comm subtraction measures nothing)."""
    mesh = build_mesh(MeshConfig(dp=8))
    state, opt, shardings, loss_fn, batch = _toy_setup(mesh)
    state2, *_ = _toy_setup(mesh)
    buck = make_bucketed_train_step(loss_fn, opt, mesh, shardings, state,
                                    batch, bucket_bytes=200,
                                    update_shard=False)
    nored = make_bucketed_train_step(loss_fn, opt, mesh, shardings, state2,
                                     batch, bucket_bytes=200, reduce=False)
    assert nored.update_sharded is False  # forced off on the twin
    assert _asked_allreduces(nored, state2, mesh, batch) \
        < _asked_allreduces(buck, state, mesh, batch)


def test_indivisible_batch_fails_like_monolithic():
    """Batch-leading-dim divisibility by the data world is a PRE-EXISTING
    repo constraint (device_put with a NamedSharding enforces it before
    either step runs); the bucketed step must not change that contract in
    either direction."""
    mesh = build_mesh(MeshConfig(dp=8))
    state_m, opt, shardings, loss_fn, batch = _toy_setup(mesh)
    state_b, *_ = _toy_setup(mesh)
    short = {"ids": batch["ids"][:12], "y": batch["y"][:12]}  # 12 % 8 != 0
    mono = make_train_step(loss_fn, opt, mesh, shardings, state_m, batch,
                           bucketed=False)
    buck = make_bucketed_train_step(loss_fn, opt, mesh, shardings, state_b,
                                    batch, bucket_bytes=200)
    for step, state in ((mono, state_m), (buck, state_b)):
        with pytest.raises(ValueError):
            step(state, shard_batch(mesh, short))


# -- comm model ---------------------------------------------------------------


def test_ideal_serial_allreduce_seconds():
    # 8 devices, 100 MB grads, 10 GB/s delivered: 2*S*(n-1)/n / bw
    s = ideal_serial_allreduce_seconds(100e6, 8, 10.0)
    np.testing.assert_allclose(s, 2 * 100e6 * 7 / 8 / 10e9)
    assert ideal_serial_allreduce_seconds(100e6, 1, 10.0) is None
    assert ideal_serial_allreduce_seconds(100e6, 8, None) is None
    assert ideal_serial_allreduce_seconds(0, 8, 10.0) is None


def test_flight_allreduce_stage_classifies_comm_bound():
    from tensorflowonspark_tpu.obs import flight

    assert flight.classify({"allreduce": 0.8, "compute": 0.1}) == \
        "comm_bound"
    assert "comm_bound" in flight.VERDICTS


# -- trainer / elastic composition --------------------------------------------


def test_trainer_uses_bucketed_step_by_default(monkeypatch):
    from tensorflowonspark_tpu.trainer import Trainer

    t = Trainer("mnist_mlp", mesh_config=MeshConfig(dp=8))
    assert getattr(t.train_step, "bucketed", False) is True
    assert t.train_step.comm_bytes > 0
    assert t.train_step.data_world == 8
    batch = t.module_lib.example_batch(t.config, batch_size=16)
    losses = [float(t.step(batch)) for _ in range(3)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    # env opt-out restores the monolithic step
    monkeypatch.setenv("TFOS_BUCKETED_ALLREDUCE", "0")
    t2 = Trainer("mnist_mlp", mesh_config=MeshConfig(dp=8))
    assert getattr(t2.train_step, "bucketed", True) is False


def test_trainer_widedeep_custom_step_keeps_its_own_path():
    """A model-prescribed sharded step (wide&deep's sparse embedding
    update) opts out of the generic dispatch entirely."""
    from tensorflowonspark_tpu.trainer import Trainer

    t = Trainer("wide_deep", mesh_config=MeshConfig(dp=8))
    assert getattr(t.train_step, "bucketed", False) is False
    batch = t.module_lib.example_batch(t.config, batch_size=16)
    assert np.isfinite(float(t.step(batch)))


def test_elastic_regroup_at_step_boundary_through_bucketed_step():
    """``Trainer.attach_elastic``'s between-steps regroup check rides the
    bucketed step unchanged: the step that observes the pending flag
    completes (metrics + callbacks included) before RegroupSignal."""
    from tensorflowonspark_tpu import elastic
    from tensorflowonspark_tpu.trainer import Trainer

    t = Trainer("mnist_mlp", mesh_config=MeshConfig(dp=8))
    assert t.train_step.bucketed is True

    class _Worker:
        pending = False

        def regroup_pending(self):
            return self.pending

        def command(self):
            return {"generation": 1, "reason": "test"}

    worker = _Worker()
    t.attach_elastic(worker)
    batch = t.module_lib.example_batch(t.config, batch_size=16)
    seen = []
    t.add_step_callback(lambda loss, n, dt: seen.append(n))
    assert np.isfinite(float(t.step(batch)))
    worker.pending = True
    with pytest.raises(elastic.RegroupSignal) as ei:
        t.step(batch)
    assert ei.value.command["generation"] == 1
    assert len(seen) == 2  # the interrupted step's callbacks still ran


def test_trainer_resnet_batchnorm_trains_through_bucketed_step():
    """Real flax BatchNorm (train-mode batch stats) composes with the
    bucketed step: per-replica statistics with cross-replica-averaged
    running stats — the DDP discipline — still trains to decreasing
    loss, and the running stats still update."""
    from tensorflowonspark_tpu.models import resnet
    from tensorflowonspark_tpu.trainer import Trainer

    config = resnet.Config.tiny(norm="batch")
    t = Trainer("resnet50", config=config, mesh_config=MeshConfig(dp=8),
                learning_rate=1e-2)
    assert t.train_step.bucketed is True
    stats0 = jax.tree_util.tree_map(
        np.asarray, t.state.collections["batch_stats"])
    batch = t.module_lib.example_batch(config, batch_size=16)
    losses = [float(t.step(batch)) for _ in range(4)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    changed = jax.tree_util.tree_map(
        lambda a, b: not np.allclose(a, np.asarray(b)),
        stats0, t.state.collections["batch_stats"])
    assert any(jax.tree_util.tree_leaves(changed))


# -- sharded weight update (reduce-scatter buckets) ---------------------------


class _ShapedLeaf:
    """Fake leaf with shape/dtype for partitioner + eligibility units."""

    def __init__(self, shape, dtype=np.float32):
        self.shape = tuple(shape)
        self.size = int(np.prod(shape)) if shape else 1
        self.dtype = np.dtype(dtype)


def test_partition_respects_key_boundaries():
    """Satellite: a bucket never mixes dtypes (or scatter/replicated
    kinds) — keys close the open bucket even below the byte bound."""
    kb = 1024
    f32 = [_Leaf(2 * kb) for _ in range(2)]
    leaves = f32 + [_Leaf(2 * kb), _Leaf(2 * kb)]
    keys = ["f32", "f32", "bf16", "bf16"]
    assert partition_buckets(leaves, 100 * kb, keys=keys) == [[0, 1], [2, 3]]
    # interleaved keys force singleton buckets
    keys = ["f32", "bf16", "f32", "bf16"]
    assert partition_buckets(leaves, 100 * kb, keys=keys) == \
        [[0], [1], [2], [3]]
    # keys=None preserves the PR 12 behaviour exactly
    assert partition_buckets(leaves, 100 * kb) == [[0, 1, 2, 3]]


def test_update_shard_eligibility_shape_policy():
    from tensorflowonspark_tpu import shapes

    # dim-0 must divide the world (row-major flat block k == dim-0 rows
    # slice k only then), size floor in BYTES, scalars/world<2 never
    assert shapes.update_shard_eligible((16, 8), 4, 8, 256)
    assert not shapes.update_shard_eligible((16, 8), 4, 8, 1024)  # too small
    assert not shapes.update_shard_eligible((12, 8), 4, 8, 256)  # 12 % 8
    assert not shapes.update_shard_eligible((), 4, 8, 1)  # scalar
    assert not shapes.update_shard_eligible((16, 8), 4, 1, 1)  # world 1
    # non-float leaves are excluded at the collectives layer
    assert not collectives.scatter_eligible(
        _ShapedLeaf((16, 8), np.int32), 8, 256)
    assert collectives.scatter_eligible(_ShapedLeaf((16, 8)), 8, 256)


def test_zero_min_bytes_env_knob(monkeypatch):
    """Satellite: ``TFOS_ZERO_MIN_BYTES`` drives BOTH the ZeRO sharding
    floor and the scatter-eligibility floor — one knob, one boundary, so
    a leaf below it rides replicated on both planes."""
    from tensorflowonspark_tpu.parallel import train

    monkeypatch.delenv("TFOS_ZERO_MIN_BYTES", raising=False)
    assert train.zero_min_bytes() == train.DEFAULT_ZERO_MIN_BYTES
    monkeypatch.setenv("TFOS_ZERO_MIN_BYTES", "4096")
    assert train.zero_min_bytes() == 4096
    leaf = _ShapedLeaf((16, 32))  # 2048 B < 4096
    assert not collectives.scatter_eligible(leaf, 8, train.zero_min_bytes())
    monkeypatch.setenv("TFOS_ZERO_MIN_BYTES", "1024")
    assert collectives.scatter_eligible(leaf, 8, train.zero_min_bytes())
    # apply_zero_sharding honours the same floor (bytes, not elements)
    mesh = build_mesh(MeshConfig(dp=2, fsdp=4))
    params = {"big": jnp.zeros((16, 32), jnp.float32),
              "tiny": jnp.zeros((8,), jnp.float32)}
    shardings = infer_param_sharding(params, mesh, min_dim=1)
    z = apply_zero_sharding(shardings, mesh, params)
    assert "fsdp" in str(z["big"].spec)
    assert "fsdp" not in str(z["tiny"].spec)
    monkeypatch.setenv("TFOS_ZERO_MIN_BYTES", str(1 << 30))
    z = apply_zero_sharding(shardings, mesh, params)
    assert "fsdp" not in str(z["big"].spec)


def _hlo_counts(step, state, mesh, batch):
    hlo = step.lower(state, shard_batch(mesh, batch)).compile().as_text()
    return {op: hlo.count(op + "(") + hlo.count(op + "-start(")
            for op in ("reduce-scatter", "all-gather", "all-reduce")}


def test_sharded_step_hlo_reduce_scatter_per_bucket():
    """The tentpole structural claim: one reduce-scatter + one all-gather
    per bucket (scatter and replicated alike) and per stats segment, and
    ZERO all-reduce ops anywhere in the lowered module."""
    mesh = build_mesh(MeshConfig(dp=8))
    state, opt, shardings, loss_fn, batch = _toy_setup(mesh)
    step = make_bucketed_train_step(loss_fn, opt, mesh, shardings, state,
                                    batch, bucket_bytes=200,
                                    update_shard=True, scatter_min_bytes=128)
    assert step.update_sharded is True
    assert step.n_scatter_buckets >= 1 and step.n_replicated_buckets >= 0
    n_segments = (step.n_scatter_buckets + step.n_replicated_buckets
                  + step.n_stats_segments)
    counts = _hlo_counts(step, state, mesh, batch)
    assert counts["all-reduce"] == 0, counts
    assert counts["reduce-scatter"] == n_segments * step.n_tiers, \
        (counts, n_segments)
    assert counts["all-gather"] == n_segments * step.n_tiers, \
        (counts, n_segments)


def test_sharded_step_hlo_stateful_has_no_allreduce():
    """Collections ride the scatter+gather stats segments — even the
    BatchNorm running-stats exchange must not reintroduce all-reduce."""
    mesh = build_mesh(MeshConfig(dp=8))
    state, opt, shardings, loss_fn, batch = _toy_setup(mesh, stateful=True)
    step = make_bucketed_train_step(loss_fn, opt, mesh, shardings, state,
                                    batch, bucket_bytes=200,
                                    update_shard=True, scatter_min_bytes=128)
    assert step.n_stats_segments == 2  # loss + one f32 collection group
    counts = _hlo_counts(step, state, mesh, batch)
    assert counts["all-reduce"] == 0, counts
    n_segments = (step.n_scatter_buckets + step.n_replicated_buckets
                  + step.n_stats_segments)
    assert counts["reduce-scatter"] == n_segments, (counts, n_segments)


def _assert_sharded_matches_allreduce(mesh, zero=False, stateful=False,
                                      steps=5, mesh_config=None,
                                      donate=True):
    """Sharded-update step vs the PR 12 bucketed all-reduce step: same
    losses, params, and collections at the established tolerances."""
    state_a, opt, shardings, loss_fn, batch = _toy_setup(
        mesh, zero=zero, stateful=stateful)
    state_s, *_ = _toy_setup(mesh, zero=zero, stateful=stateful)
    allred = make_bucketed_train_step(
        loss_fn, opt, mesh, shardings, state_a, batch, bucket_bytes=200,
        update_shard=False, donate=donate)
    shard = make_bucketed_train_step(
        loss_fn, opt, mesh, shardings, state_s, batch, bucket_bytes=200,
        update_shard=True, scatter_min_bytes=128, mesh_config=mesh_config,
        donate=donate)
    assert shard.update_sharded and shard.n_scatter_buckets >= 1
    sharded = shard_batch(mesh, batch)
    for _ in range(steps):
        state_a, loss_a = allred(state_a, sharded)
        state_s, loss_s = shard(state_s, sharded)
        np.testing.assert_allclose(float(loss_a), float(loss_s), **TOL)
    for key in state_a.params:
        np.testing.assert_allclose(np.asarray(state_a.params[key]),
                                   np.asarray(state_s.params[key]),
                                   err_msg=key, **TOL)
    if stateful:
        np.testing.assert_allclose(
            np.asarray(state_a.collections["stats"]["mean"]),
            np.asarray(state_s.collections["stats"]["mean"]), **TOL)
        assert int(state_s.collections["stats"]["count"]) == steps
    return state_s


def test_sharded_matches_allreduce_dp_only():
    _assert_sharded_matches_allreduce(build_mesh(MeshConfig(dp=8)))


def test_sharded_matches_allreduce_dp_fsdp_zero():
    state = _assert_sharded_matches_allreduce(
        build_mesh(MeshConfig(dp=2, fsdp=4)), zero=True)
    # ZeRO param storage sharding survives the sharded-update step
    assert any(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda p: "fsdp" in str(p.sharding.spec), state.params)))


def test_sharded_matches_allreduce_stateful_batchnorm():
    _assert_sharded_matches_allreduce(build_mesh(MeshConfig(dp=8)),
                                      stateful=True)


def test_sharded_matches_allreduce_no_donation():
    _assert_sharded_matches_allreduce(build_mesh(MeshConfig(dp=8)),
                                      donate=False, steps=3)


def test_sharded_opt_state_is_scatter_sharded():
    """The composition claim: optimizer moments of scatter-eligible params
    are STORED as dim-0 shards over the scatter axes, so the scattered
    gradient block and its opt state meet on-device with no reshard."""
    mesh = build_mesh(MeshConfig(dp=2, fsdp=4))
    state, opt, shardings, loss_fn, batch = _toy_setup(mesh)
    step = make_bucketed_train_step(loss_fn, opt, mesh, shardings, state,
                                    batch, bucket_bytes=200,
                                    update_shard=True, scatter_min_bytes=128)
    state, _ = step(state, shard_batch(mesh, batch))
    mu = state.opt_state[0].mu  # adamw first moment, param-tree shaped
    specs = {k: str(v.sharding.spec) for k, v in mu.items()}
    # the big eligible leaf shards over BOTH data axes; the scalar-ish
    # count leaf stays replicated
    assert any("dp" in s and "fsdp" in s for s in specs.values()), specs
    count = state.opt_state[0].count
    assert "dp" not in str(count.sharding.spec)


def test_sharded_update_env_opt_out(monkeypatch):
    mesh = build_mesh(MeshConfig(dp=8))
    state, opt, shardings, loss_fn, batch = _toy_setup(mesh)
    monkeypatch.setenv("TFOS_SHARDED_UPDATE", "0")
    step = make_bucketed_train_step(loss_fn, opt, mesh, shardings, state,
                                    batch, bucket_bytes=200)
    assert step.update_sharded is False
    assert _asked_allreduces(step, state, mesh, batch) == step.n_buckets + 1
    monkeypatch.delenv("TFOS_SHARDED_UPDATE")
    step = make_bucketed_train_step(loss_fn, opt, mesh, shardings, state,
                                    batch, bucket_bytes=200,
                                    scatter_min_bytes=128)
    assert step.update_sharded is True


# -- two-tier (ICI/DCN) staging -----------------------------------------------


def test_scatter_stages_single_and_two_tier():
    mesh = build_mesh(MeshConfig(dp=2, fsdp=4))
    stages, dcn_world, reason = collectives.scatter_stages(mesh, None)
    assert stages == [("dp", "fsdp")] and dcn_world == 1
    # pure cross-slice dp axis → two tiers: fsdp in-slice, dp over DCN
    cfg = MeshConfig(dp=2, fsdp=4, slices=2)
    stages, dcn_world, reason = collectives.scatter_stages(
        build_mesh(cfg), cfg)
    assert stages == [("fsdp",), ("dp",)] and dcn_world == 2
    assert reason is None
    # dp bigger than slices: the axis mixes in-slice and cross-slice
    # neighbours — single-tier fallback with the reason recorded
    cfg = MeshConfig(dp=4, fsdp=2, slices=2)
    stages, dcn_world, reason = collectives.scatter_stages(
        build_mesh(cfg), cfg)
    assert stages == [("dp", "fsdp")] and dcn_world == 1
    assert reason and "single-tier" in reason


def test_two_tier_sharded_step_matches_allreduce():
    """On the 2-slice virtual mesh the staged (per-tier) exchange is
    numerically identical to the flat one, its HLO carries one
    reduce-scatter + all-gather per segment PER TIER, and still zero
    all-reduce."""
    cfg = MeshConfig(dp=2, fsdp=4, slices=2)
    mesh = build_mesh(cfg)
    state = _assert_sharded_matches_allreduce(mesh, mesh_config=cfg)
    state2, opt, shardings, loss_fn, batch = _toy_setup(mesh)
    step = make_bucketed_train_step(loss_fn, opt, mesh, shardings, state2,
                                    batch, bucket_bytes=200,
                                    update_shard=True, scatter_min_bytes=128,
                                    mesh_config=cfg)
    assert step.n_tiers == 2 and step.dcn_world == 2
    assert step.scatter_axes == ("fsdp", "dp")
    counts = _hlo_counts(step, state2, mesh, batch)
    n_segments = (step.n_scatter_buckets + step.n_replicated_buckets
                  + step.n_stats_segments)
    assert counts["all-reduce"] == 0, counts
    assert counts["reduce-scatter"] == n_segments * 2, (counts, n_segments)


def test_dcn_bucket_bytes_default(monkeypatch):
    monkeypatch.setenv("TFOS_DCN_BUCKET_MB", "16")
    assert collectives.dcn_bucket_bytes_default() == 16 * 1024 * 1024
    monkeypatch.delenv("TFOS_DCN_BUCKET_MB")
    # no override → the ratio over the ICI bound
    assert collectives.dcn_bucket_bytes_default() == min(
        int(collectives.bucket_bytes_default()
            * collectives.DEFAULT_DCN_BUCKET_RATIO),
        collectives._DCN_BUCKET_CAP)


# -- analytic bytes model -----------------------------------------------------


def test_collective_bytes_model_scatter_halves_exchange():
    """Acceptance: scatter-path exchange bytes < allreduce for every >=2
    device config, → ½ asymptotically as the eligible fraction → 1."""
    leaves = [_ShapedLeaf((1024, 256))]  # 1 MB, fully eligible
    for world in (2, 4, 8, 64):
        m = collectives.collective_bytes_per_step(
            leaves, world, scatter_min_bytes=1024)
        assert m["scatter"]["exchange"] < m["allreduce"]["exchange"], world
        assert 0 < m["exchange_ratio"] < 1
    m = collectives.collective_bytes_per_step(
        leaves, 64, scatter_min_bytes=1024)
    np.testing.assert_allclose(m["exchange_ratio"], 0.5, atol=0.01)
    # totals converge: the win is the halved exchange leg (serialized
    # against backward), not fewer total wire bytes
    assert m["scatter"]["total"] <= m["allreduce"]["total"] * 1.01


def test_collective_bytes_model_ineligible_and_off():
    leaves = [_ShapedLeaf((7, 8)), _ShapedLeaf((3,))]  # nothing eligible
    m = collectives.collective_bytes_per_step(leaves, 8,
                                              scatter_min_bytes=1)
    assert m["n_scatter_leaves"] == 0
    # all-replicated tree: the scatter path pays the loss/stats segment
    # ON TOP of the same grad bytes — the model reports the (slight)
    # regression honestly instead of rounding it to parity
    assert m["exchange_ratio"] >= 1.0
    m = collectives.collective_bytes_per_step(
        [_ShapedLeaf((1024, 256))], 8, scatter_min_bytes=1,
        update_shard=False)
    assert m["update_shard"] is False
    np.testing.assert_allclose(m["exchange_ratio"], 1.0)


def test_collective_bytes_model_tier_split():
    leaves = [_ShapedLeaf((1024, 256))]
    m = collectives.collective_bytes_per_step(
        leaves, 8, scatter_min_bytes=1024, dcn_world=2)
    assert m["ici_world"] == 4 and m["dcn_world"] == 2
    for path in ("allreduce", "scatter"):
        p = m[path]
        np.testing.assert_allclose(
            p["exchange_ici"] + p["exchange_dcn"], p["exchange"])
        assert p["exchange_dcn"] > 0
    # staged split sums to the flat ring total: S·(N-1)/N per pass
    flat = collectives.collective_bytes_per_step(
        leaves, 8, scatter_min_bytes=1024, dcn_world=1)
    np.testing.assert_allclose(m["allreduce"]["exchange"],
                               flat["allreduce"]["exchange"])


def test_step_comm_model_attr_matches_module_fn():
    mesh = build_mesh(MeshConfig(dp=8))
    state, opt, shardings, loss_fn, batch = _toy_setup(mesh)
    step = make_bucketed_train_step(loss_fn, opt, mesh, shardings, state,
                                    batch, bucket_bytes=200,
                                    update_shard=True, scatter_min_bytes=128)
    m = step.comm_model
    assert m["world"] == 8 and m["update_shard"] is True
    assert m["scatter_bytes"] + m["replicated_bytes"] == m["grad_bytes"]
    assert m["grad_bytes"] == step.comm_bytes
    assert 0 < m["exchange_ratio"] < 1


# -- global-norm clipping (the lifted TFOS_SHARDED_UPDATE=0 carve-out) --------


def test_clip_global_norm_matches_optax_chain():
    """``clip_global_norm=`` on the monolithic step must reproduce the
    stock ``optax.chain(clip_by_global_norm, adamw)`` step — pins our
    manual clip to optax's exact definition (the ``(g / norm) * max``
    scaling behind a ``norm < max`` trigger, no eps variant)."""
    import optax

    mesh = build_mesh(MeshConfig(dp=8))
    clip = 1e-2
    state_c, opt, shardings, loss_fn, batch = _toy_setup(mesh)
    state_r, *_ = _toy_setup(mesh)
    chained = optax.chain(optax.clip_by_global_norm(clip), optax.adamw(5e-2))
    state_r = create_train_state(state_r.params, chained)
    step_c = make_train_step(loss_fn, opt, mesh, shardings, state_c, batch,
                             bucketed=False, clip_global_norm=clip)
    step_r = make_train_step(loss_fn, chained, mesh, shardings, state_r,
                             batch, bucketed=False)
    assert step_c.clip_global_norm == clip
    sharded = shard_batch(mesh, batch)
    for _ in range(3):
        state_c, loss_c = step_c(state_c, sharded)
        state_r, loss_r = step_r(state_r, sharded)
        np.testing.assert_allclose(float(loss_c), float(loss_r), **TOL)
    for key in state_c.params:
        np.testing.assert_allclose(np.asarray(state_c.params[key]),
                                   np.asarray(state_r.params[key]),
                                   err_msg=key, **TOL)


def _assert_clip_matches(mesh, clip, zero=False, steps=5, update_shard=True):
    """Clipped sharded-update (or all-reduce) bucketed step vs the clipped
    monolithic step: same losses and params at the established tolerances."""
    state_m, opt, shardings, loss_fn, batch = _toy_setup(mesh, zero=zero)
    state_s, *_ = _toy_setup(mesh, zero=zero)
    mono = make_train_step(loss_fn, opt, mesh, shardings, state_m, batch,
                           bucketed=False, clip_global_norm=clip)
    shard = make_bucketed_train_step(
        loss_fn, opt, mesh, shardings, state_s, batch, bucket_bytes=200,
        update_shard=update_shard, scatter_min_bytes=128,
        clip_global_norm=clip)
    if update_shard:
        assert shard.update_sharded and shard.n_scatter_buckets >= 1
    assert shard.clip_global_norm == clip
    sharded = shard_batch(mesh, batch)
    for _ in range(steps):
        state_m, loss_m = mono(state_m, sharded)
        state_s, loss_s = shard(state_s, sharded)
        np.testing.assert_allclose(float(loss_m), float(loss_s), **TOL)
    for key in state_m.params:
        np.testing.assert_allclose(np.asarray(state_m.params[key]),
                                   np.asarray(state_s.params[key]),
                                   err_msg=key, **TOL)
    return state_s


def test_sharded_clip_matches_monolithic_dp_only():
    """The lifted carve-out, active regime: a clip small enough to fire
    every step — the sharded-update step's rs+ag global norm must equal
    the monolithic step's full-gradient norm."""
    mesh = build_mesh(MeshConfig(dp=8))
    clip = 1e-2
    state_s = _assert_clip_matches(mesh, clip)
    # the clip genuinely fired: an unclipped twin lands elsewhere
    state_u, opt, shardings, loss_fn, batch = _toy_setup(mesh)
    unclipped = make_bucketed_train_step(
        loss_fn, opt, mesh, shardings, state_u, batch, bucket_bytes=200,
        update_shard=True, scatter_min_bytes=128)
    sharded = shard_batch(mesh, batch)
    for _ in range(5):
        state_u, _ = unclipped(state_u, sharded)
    assert not np.allclose(np.asarray(state_s.params["emb"]),
                           np.asarray(state_u.params["emb"]), **TOL)


def test_sharded_clip_matches_monolithic_zero():
    _assert_clip_matches(build_mesh(MeshConfig(dp=2, fsdp=4)), 1e-2,
                         zero=True)


def test_sharded_clip_inactive_regime():
    """A threshold far above any real gradient norm: the clipped sharded
    step must reduce to the unclipped one (the ``norm < max`` trigger
    path, where the scale is exactly 1)."""
    mesh = build_mesh(MeshConfig(dp=8))
    state_c, opt, shardings, loss_fn, batch = _toy_setup(mesh)
    state_u, *_ = _toy_setup(mesh)
    clipped = make_bucketed_train_step(
        loss_fn, opt, mesh, shardings, state_c, batch, bucket_bytes=200,
        update_shard=True, scatter_min_bytes=128, clip_global_norm=1e6)
    unclipped = make_bucketed_train_step(
        loss_fn, opt, mesh, shardings, state_u, batch, bucket_bytes=200,
        update_shard=True, scatter_min_bytes=128)
    sharded = shard_batch(mesh, batch)
    for _ in range(3):
        state_c, loss_c = clipped(state_c, sharded)
        state_u, loss_u = unclipped(state_u, sharded)
        np.testing.assert_allclose(float(loss_c), float(loss_u), **TOL)
    for key in state_c.params:
        np.testing.assert_allclose(np.asarray(state_c.params[key]),
                                   np.asarray(state_u.params[key]),
                                   err_msg=key, **TOL)


def test_allreduce_path_clip_matches_monolithic():
    """update_shard=False keeps full gradients outside the region, so the
    clip there is the stock optax transform — still must match."""
    _assert_clip_matches(build_mesh(MeshConfig(dp=8)), 1e-2,
                         update_shard=False)


def test_clipped_sharded_step_hlo_has_no_allreduce():
    """The point of the satellite: clipping must NOT knock the step off
    the reduce-scatter path.  The norm's cross-replica sum rides one
    extra scalar rs+ag segment; zero all-reduce ops in the module."""
    mesh = build_mesh(MeshConfig(dp=8))
    state, opt, shardings, loss_fn, batch = _toy_setup(mesh)
    step = make_bucketed_train_step(loss_fn, opt, mesh, shardings, state,
                                    batch, bucket_bytes=200,
                                    update_shard=True, scatter_min_bytes=128,
                                    clip_global_norm=1e-2)
    counts = _hlo_counts(step, state, mesh, batch)
    assert counts["all-reduce"] == 0, counts
    n_segments = (step.n_scatter_buckets + step.n_replicated_buckets
                  + step.n_stats_segments + 1)  # +1: the norm's rs+ag
    assert counts["reduce-scatter"] == n_segments * step.n_tiers, \
        (counts, n_segments)
    assert counts["all-gather"] == n_segments * step.n_tiers, \
        (counts, n_segments)
