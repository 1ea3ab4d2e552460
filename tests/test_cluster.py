"""End-to-end cluster tests on the local substrate (SURVEY.md §4: the
local-cluster trick — real processes, real rendezvous, real queues, JAX on
the CPU backend)."""

import sys
import time

import cloudpickle
import numpy as np
import pytest

from tensorflowonspark_tpu import TFCluster, TFManager
from tensorflowonspark_tpu.sparkapi import LocalSparkContext

# ship this test module by value so spawned executors/trainers don't need to
# import it by name
cloudpickle.register_pickle_by_value(sys.modules[__name__])


@pytest.fixture()
def sc():
    ctx = LocalSparkContext("local-cluster[2,1,1024]", "cluster-test")
    yield ctx
    ctx.stop()


def _make_regression_data(n=512, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    w_true = np.array([2.0, -1.0, 0.5, 3.0], dtype=np.float32)
    y = x @ w_true + 1.0
    return [(x[i], float(y[i])) for i in range(n)]


def linear_train_fun(args, ctx):
    """Train y = w·x + b by SGD from the Spark feed; record final loss."""
    from tensorflowonspark_tpu import util

    util.ensure_jax_platform()
    import jax
    import jax.numpy as jnp

    feed = ctx.get_data_feed(train_mode=True, input_mapping=["x", "y"])

    @jax.jit
    def step(w, b, x, y):
        def loss_fn(w, b):
            pred = x @ w + b
            return jnp.mean((pred - y) ** 2)

        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(w, b)
        return w - 0.1 * grads[0], b - 0.1 * grads[1], loss

    w = jnp.zeros(4)
    b = jnp.asarray(0.0)
    loss = None
    while not feed.should_stop():
        batch = feed.next_batch(64)
        if not batch or batch["x"].shape[0] == 0:
            continue
        w, b, loss = step(w, b, batch["x"], batch["y"])
    ctx.mgr.set("final_loss", float(loss))
    ctx.mgr.set("final_w", np.asarray(w).tolist())


def predict_fun(args, ctx):
    """Inference map_fun: doubles each input value."""
    from tensorflowonspark_tpu import util

    util.ensure_jax_platform()
    import jax

    feed = ctx.get_data_feed(train_mode=False, input_mapping=["x"])
    double = jax.jit(lambda x: x * 2.0)
    while not feed.should_stop():
        batch = feed.next_batch(16)
        if not batch or batch["x"].shape[0] == 0:
            continue
        feed.batch_results(np.asarray(double(batch["x"])).tolist())


def failing_fun(args, ctx):
    raise ValueError("synthetic map_fun failure")


def tf_mode_fun(args, ctx):
    """TENSORFLOW-mode map_fun: no Spark feed; reads own 'dataset'."""
    ctx.mgr.set("ran_executor", ctx.executor_id)
    ctx.mgr.set("job", f"{ctx.job_name}:{ctx.task_index}")


def test_spark_mode_train_end_to_end(sc):
    data = _make_regression_data()
    cluster = TFCluster.run(sc, linear_train_fun, tf_args=None, num_executors=2,
                            input_mode=TFCluster.InputMode.SPARK)
    rdd = sc.parallelize(data, 2)
    cluster.train(rdd, num_epochs=4, feed_timeout=120)
    cluster.shutdown(grace_secs=30)

    # read each node's final loss straight from its manager (same host)
    authkey = bytes.fromhex(cluster.cluster_meta["authkey_hex"])
    for meta in cluster.cluster_info:
        mgr = TFManager.connect(tuple(meta["addr"]), authkey)
        assert mgr.get("state") == "finished"
        final_loss = mgr.get("final_loss")
        assert final_loss is not None and final_loss < 1.0, (
            f"executor {meta['executor_id']}: loss {final_loss}"
        )
        w = np.asarray(mgr.get("final_w"))
        np.testing.assert_allclose(w, [2.0, -1.0, 0.5, 3.0], atol=0.5)


def metered_train_fun(args, ctx):
    """linear_train_fun + a MetricsReporter publishing every step."""
    from tensorflowonspark_tpu import util

    util.ensure_jax_platform()
    import time

    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.metrics import MetricsReporter

    feed = ctx.get_data_feed(train_mode=True, input_mapping=["x", "y"])
    reporter = MetricsReporter(ctx, interval=1)

    @jax.jit
    def step(w, b, x, y):
        def loss_fn(w, b):
            return jnp.mean((x @ w + b - y) ** 2)

        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(w, b)
        return w - 0.1 * grads[0], b - 0.1 * grads[1], loss

    w, b, t_prev = jnp.zeros(4), jnp.asarray(0.0), time.perf_counter()
    while not feed.should_stop():
        batch = feed.next_batch(32)
        if not batch or batch["x"].shape[0] == 0:
            continue
        w, b, loss = step(w, b, batch["x"], batch["y"])
        now = time.perf_counter()
        reporter(loss, int(batch["x"].shape[0]), now - t_prev)
        # the straggler-detector feed (Trainer does this automatically;
        # hand-rolled loops instrument the same histogram)
        from tensorflowonspark_tpu import obs

        obs.histogram("trainer_step_seconds").observe(now - t_prev)
        t_prev = now
        time.sleep(0.02)  # give the driver poller time to observe us
    reporter.publish()


def test_train_time_metrics_polling_and_stale_retention(sc):
    """VERDICT r3 weak #5: the driver samples metrics DURING train into
    cluster.metrics_history; after shutdown the (dead) nodes' final
    snapshots survive as stale entries with a weighted mean_loss."""
    data = _make_regression_data(n=768)
    cluster = TFCluster.run(sc, metered_train_fun, tf_args=None,
                            num_executors=2,
                            input_mode=TFCluster.InputMode.SPARK)
    cluster.train(sc.parallelize(data, 2), num_epochs=4, feed_timeout=120,
                  metrics_interval=0.3)
    # polled during training: history has samples, nodes reported steps
    assert cluster.metrics_history, "poller never sampled during train"
    last = cluster.metrics_history[-1][1]
    assert last["num_reporting"] >= 1
    live = cluster.metrics()  # managers still up: fresh snapshots
    assert live["num_reporting"] == 2
    assert live["mean_loss"] is not None
    for snap in live["nodes"].values():
        assert snap["step"] > 0 and snap["total_examples"] > 0
    # ISSUE 3 acceptance: per-node step-time histograms reached the driver
    # rollup — each node's own p50/p95 is in the aggregate, and the
    # straggler detector judges them (uniform local nodes: no findings)
    assert set(live["step_time_quantiles"]) == {"worker:0", "worker:1"}
    for q in live["step_time_quantiles"].values():
        assert q["p50"] > 0 and q["p95"] >= q["p50"]
    report = cluster.check_anomalies(live)
    assert report["num_nodes"] == 2

    cluster.shutdown(grace_secs=30)
    # simulate the managers dying (on a real cluster the executor process
    # exits; the local substrate keeps them up): unreachable addresses must
    # yield the retained last snapshots, stale-marked, not silent drops
    for meta in cluster.cluster_info:
        meta["addr"] = ("127.0.0.1", 1)  # nothing listens there
    after = cluster.metrics()
    assert after["num_reporting"] == 2
    assert all(s.get("stale") for s in after["nodes"].values())
    assert after["total_examples_per_sec"] is None  # no live throughput
    assert after["mean_loss"] is not None


def test_dump_trace_merges_driver_and_executors(sc, tmp_path):
    """ISSUE 1 acceptance: TFCluster.dump_trace() produces ONE Chrome-trace
    file merging the driver and ≥2 executor nodes — lifecycle spans from
    the driver (reserve/train/shutdown), the bootstrap tasks
    (manager_start/register_await), and the spawned trainers (map_fun),
    all shipped over the TFManager kv blackboard, schema-valid per
    tools/check_trace.py."""
    import json
    import os

    data = _make_regression_data(n=256)
    cluster = TFCluster.run(sc, metered_train_fun, tf_args=None,
                            num_executors=2,
                            input_mode=TFCluster.InputMode.SPARK)
    cluster.train(sc.parallelize(data, 2), num_epochs=2, feed_timeout=120)

    # ISSUE 3 acceptance: the LIVE driver endpoint round-trips over a real
    # socket while the cluster is up — /metrics is valid Prometheus text,
    # /healthz reflects the node kv, /trace passes the trace schema gate
    import urllib.request

    from tensorflowonspark_tpu.obs import httpd as obs_httpd

    server = cluster.serve_observability(port=0)
    with urllib.request.urlopen(server.url("/metrics"), timeout=30) as r:
        assert r.status == 200
        assert r.headers["Content-Type"] == obs_httpd.PROMETHEUS_CONTENT_TYPE
        metrics_text = r.read().decode()
    assert 'tfos_node_step{node="worker:0"}' in metrics_text
    assert obs_httpd.validate_prometheus_text(metrics_text) == []
    with urllib.request.urlopen(server.url("/healthz"), timeout=30) as r:
        health = json.loads(r.read().decode())
        assert r.status == 200
        assert set(health["nodes"]) == {"worker:0", "worker:1"}
    with urllib.request.urlopen(server.url("/trace"), timeout=30) as r:
        live_trace = json.loads(r.read().decode())
    # ISSUE 6: the /pipeline flight-recorder view round-trips live — the
    # executors' DataFeed wait/ingest stage histograms shipped with their
    # metrics publications and render per node
    with urllib.request.urlopen(server.url("/pipeline"), timeout=30) as r:
        assert r.status == 200
        pipeline_doc = json.loads(r.read().decode())
    assert "planes" in pipeline_doc and "node_runtime" in pipeline_doc
    feed_nodes = pipeline_doc["planes"]["feed"]["nodes"]
    assert set(feed_nodes) == {"worker:0", "worker:1"}
    for doc in feed_nodes.values():
        assert "wait" in doc["stages"]

    # straggler/stall judgment runs on live cluster state without error
    # (2 healthy uniform nodes: no findings — and no feed-starvation
    # finding, since the hand-rolled loop commits no flight verdicts)
    report = cluster.check_anomalies()
    assert report["stalled"] == [] and report["stall_events"] == []
    assert report["feed_starved"] == []

    metrics_url = server.url("/metrics")
    cluster.shutdown(grace_secs=30)
    # shutdown stops the endpoint with the cluster
    with pytest.raises(Exception):
        urllib.request.urlopen(metrics_url, timeout=2)

    path = str(tmp_path / "cluster_trace.json")
    assert cluster.dump_trace(path) == path
    with open(path) as f:
        doc = json.load(f)
    tracks = {e["args"]["name"] for e in doc["traceEvents"]
              if e["ph"] == "M"}
    assert "driver" in tracks
    assert {"worker:0", "worker:1"} <= tracks, tracks
    names = {e["name"] for e in doc["traceEvents"]}
    # driver lifecycle phases
    assert {"cluster.reserve", "cluster.train", "cluster.feed_epoch",
            "cluster.shutdown"} <= names, names
    # executor bootstrap + trainer phases (shipped via the blackboard)
    assert {"node.manager_start", "node.register_await",
            "node.map_fun"} <= names, names

    # the emitted artifact passes the tier-1 schema validator
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import check_trace

    assert check_trace.validate_doc(doc) == []
    # the live /trace document served during the run passes the same gate
    assert check_trace.validate_doc(live_trace) == []

    # generalized metrics: the same cluster serves a Prometheus exposition
    # (per-node step gauges + the merged obs registry of feed counters)
    text = cluster.metrics_prometheus()
    assert 'tfos_node_step{node="worker:0"}' in text
    assert 'tfos_node_step{node="worker:1"}' in text
    assert "# TYPE tfos_cluster_num_reporting gauge" in text
    assert "tfos_datafeed_batches_total" in text  # merged registry
    # exposition-format validity: ONE "# TYPE" line per metric family
    # (a duplicate fails the whole scrape in real Prometheus)
    type_lines = [ln for ln in text.splitlines() if ln.startswith("# TYPE")]
    assert len(type_lines) == len(set(type_lines)), type_lines


def test_spark_mode_inference_round_trip(sc):
    cluster = TFCluster.run(sc, predict_fun, tf_args=None, num_executors=2)
    values = [(float(i),) for i in range(40)]
    preds = cluster.inference(sc.parallelize(values, 4)).collect()
    cluster.shutdown(grace_secs=30)
    assert sorted(preds) == [2.0 * i for i in range(40)]


def test_map_fun_error_propagates_to_driver(sc):
    cluster = TFCluster.run(sc, failing_fun, tf_args=None, num_executors=2)
    rdd = sc.parallelize([(1.0,)] * 16, 2)
    with pytest.raises(RuntimeError, match="synthetic map_fun failure"):
        # the error surfaces on feed (trainer already dead) or at shutdown
        cluster.train(rdd, feed_timeout=30)
        cluster.shutdown(grace_secs=10)
    cluster.server.stop()


def test_tensorflow_mode_runs_to_completion(sc):
    cluster = TFCluster.run(sc, tf_mode_fun, tf_args=None, num_executors=2,
                            input_mode=TFCluster.InputMode.TENSORFLOW,
                            master_node="chief")
    cluster.shutdown(grace_secs=30)
    authkey = bytes.fromhex(cluster.cluster_meta["authkey_hex"])
    jobs = set()
    for meta in cluster.cluster_info:
        mgr = TFManager.connect(tuple(meta["addr"]), authkey)
        assert mgr.get("ran_executor") == meta["executor_id"]
        jobs.add(mgr.get("job"))
    assert jobs == {"chief:0", "worker:0"}


def test_cluster_template_roles(sc):
    cluster = TFCluster.run(sc, tf_mode_fun, tf_args=None, num_executors=2,
                            input_mode=TFCluster.InputMode.TENSORFLOW,
                            eval_node=True)
    cluster.shutdown(grace_secs=30)
    roles = {m["executor_id"]: m["job_name"] for m in cluster.cluster_info}
    assert roles == {0: "worker", 1: "evaluator"}


def test_num_executors_mismatch_rejected(sc):
    with pytest.raises(ValueError, match="num_executors"):
        TFCluster.run(sc, tf_mode_fun, tf_args=None, num_executors=5)


class FakeDStream:
    """Minimal DStream: replays pre-built RDDs through foreachRDD."""

    def __init__(self, rdds):
        self.rdds = rdds

    def foreachRDD(self, fn):
        for rdd in self.rdds:
            fn(rdd)


class FakeSSC:
    def __init__(self):
        self.stopped_with = None

    def stop(self, stopSparkContext=True, stopGraceFully=False):
        self.stopped_with = (stopSparkContext, stopGraceFully)


def test_streaming_feed_and_graceful_ssc_stop(sc):
    """train_stream feeds micro-batch RDDs through the node queues;
    shutdown(ssc=...) drains them and stops the streaming context
    gracefully (reference TFCluster.shutdown(ssc) semantics)."""
    data = _make_regression_data(n=256)
    cluster = TFCluster.run(sc, linear_train_fun, tf_args=None, num_executors=2,
                            input_mode=TFCluster.InputMode.SPARK)
    micro_batches = [sc.parallelize(data[i::4], 2) for i in range(4)] * 4
    cluster.train_stream(FakeDStream(micro_batches), feed_timeout=120)
    ssc = FakeSSC()
    cluster.shutdown(ssc=ssc, grace_secs=30)
    assert ssc.stopped_with == (False, True)

    authkey = bytes.fromhex(cluster.cluster_meta["authkey_hex"])
    for meta in cluster.cluster_info:
        mgr = TFManager.connect(tuple(meta["addr"]), authkey)
        assert mgr.get("state") == "finished"
        assert mgr.get("final_loss") < 1.0


def test_wedged_chip_fails_bootstrap_fast_and_named(monkeypatch):
    """Slice-health check at rendezvous (SURVEY §5 TPU plan, VERDICT r4 #2):
    a wedged chip — simulated by the probe child sleeping forever — must
    become a fast bootstrap failure on the driver that NAMES the sick
    executor, not a silent mesh hang bounded only by feed_timeout."""
    monkeypatch.setenv("TFOS_HEALTH_PROBE", "1")
    monkeypatch.setenv("TFOS_HEALTH_PROBE_HANG", "1")
    ctx = LocalSparkContext("local-cluster[2,1,1024]", "health-wedge-test")
    try:
        t0 = time.monotonic()
        with pytest.raises(RuntimeError,
                           match=r"executor \d .*health probe.*hung"):
            TFCluster.run(sc=ctx, map_fun=linear_train_fun, tf_args=None,
                          num_executors=2, reservation_timeout=120,
                          health_probe_timeout=3.0)
        # attributed failure arrived via the kv fast-path, well inside the
        # reservation timeout
        assert time.monotonic() - t0 < 60
    finally:
        ctx.stop()


def test_healthy_probe_passes_and_cluster_trains(monkeypatch):
    """Force-enabled probe on a healthy backend: bootstrap proceeds and the
    cluster still trains end-to-end (probe leaves no residue)."""
    monkeypatch.setenv("TFOS_HEALTH_PROBE", "1")
    monkeypatch.delenv("TFOS_HEALTH_PROBE_HANG", raising=False)
    ctx = LocalSparkContext("local-cluster[2,1,1024]", "health-ok-test")
    try:
        cluster = TFCluster.run(sc=ctx, map_fun=linear_train_fun, tf_args=None,
                                num_executors=2, health_probe_timeout=90.0)
        data = _make_regression_data(n=256)
        cluster.train(ctx.parallelize(data, 2), num_epochs=2, feed_timeout=120)
        cluster.shutdown(grace_secs=30)
        authkey = bytes.fromhex(cluster.cluster_meta["authkey_hex"])
        for meta in cluster.cluster_info:
            mgr = TFManager.connect(tuple(meta["addr"]), authkey)
            assert mgr.get("state") == "finished"
    finally:
        ctx.stop()


def test_probe_skipped_when_no_chips():
    """Default policy: zero claimed chips (the CPU test substrate) → no
    probe, zero bootstrap overhead (healthy-path requirement)."""
    from tensorflowonspark_tpu import health

    assert health.should_probe({"health_probe": None}, chips=[]) is False
    assert health.should_probe({"health_probe": None}, chips=[0]) is True
    assert health.should_probe({"health_probe": True}, chips=[]) is True
    assert health.should_probe({"health_probe": False}, chips=[0]) is False


def test_step_watchdog_fires_on_stall_and_not_on_beats():
    """Unit: an armed step that never completes trips on_stall once; beats
    keep it quiet (exit_on_stall=False so the test process survives)."""
    from tensorflowonspark_tpu import health

    fired = []
    wd = health.StepWatchdog(0.3, on_stall=fired.append,
                             exit_on_stall=False)
    try:
        # beating steps: never fires
        for _ in range(4):
            wd.arm()
            time.sleep(0.05)
            wd.beat()
        time.sleep(0.5)
        assert fired == []
        # a stall: fires exactly once, with an attributable reason
        wd.arm()
        time.sleep(1.0)
        assert len(fired) == 1 and "stalled" in fired[0]
    finally:
        wd.stop()


def test_trainer_step_watchdog_healthy_path():
    """Trainer(step_timeout_s=...) on a healthy backend: steps run, loss is
    finite, callbacks still fire, nothing trips."""
    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.trainer import Trainer

    seen = []
    t = Trainer("mnist_mlp", config=mnist.Config.tiny(), step_timeout_s=60,
                error_sink=seen.append)
    t.add_step_callback(lambda loss, n, dt: seen.append(("cb", float(loss))))
    batch = mnist.example_batch(t.config, batch_size=8)
    losses = [float(t.step(batch)) for _ in range(2)]
    assert np.isfinite(losses).all()
    assert [s for s in seen if isinstance(s, tuple)]  # callbacks ran
    assert not [s for s in seen if isinstance(s, str)]  # no stall reported


def test_trainer_watchdog_tolerates_compile_and_handled_errors():
    """The first (compiling) step of each batch shape runs unarmed — XLA
    compile minutes must not read as a wedge — and an exception the caller
    handles disarms the watchdog instead of leaving a stale timestamp that
    later fires (either failure here would os._exit the test run)."""
    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.trainer import Trainer

    reported = []
    t = Trainer("mnist_mlp", config=mnist.Config.tiny(), step_timeout_s=1,
                error_sink=reported.append)
    batch = mnist.example_batch(t.config, batch_size=8)
    t.step(batch)   # compile happens here, unarmed (takes > timeout)
    t.step(batch)   # armed steady-state step, well under the timeout
    # same keys AND shapes as the warm batch (so this step runs ARMED) but
    # an object-dtype leaf → shard/device_put raises mid-armed-window
    bad = dict(batch)
    bad["label"] = np.array(["x"] * len(np.asarray(batch["label"])))
    with pytest.raises(Exception):
        t.step(bad)
    time.sleep(1.5)  # stale armed timestamp would fire in this window
    assert reported == []


def test_mid_run_wedge_fails_fast_and_named(monkeypatch):
    """Cluster-level: a trainer whose step wedges mid-run (its compiled
    step, once warm, never returns) dies fast with the reason on the error
    queue — the driver raises an attributed error instead of hanging the
    mesh until feed_timeout."""
    # shrink the dead-executor manager's orphan lingering so the test's
    # teardown (sc.stop + interpreter exit) stays fast
    monkeypatch.setenv("TFOS_MANAGER_ORPHAN_GRACE_S", "3")

    def wedged_train_fun(args, ctx):
        from tensorflowonspark_tpu import util

        util.ensure_jax_platform()
        from tensorflowonspark_tpu.models import mnist
        from tensorflowonspark_tpu.trainer import Trainer

        t = Trainer("mnist_mlp", config=mnist.Config.tiny(),
                    step_timeout_s=3, error_sink=ctx.report_error)
        batch = mnist.example_batch(t.config, batch_size=8)
        t.step(batch)  # first step: compile warm-up, runs unarmed

        def wedged_step(state, staged):
            import time

            time.sleep(3600)

        t.train_step = wedged_step
        t.step(batch)  # second step arms, then wedges — never returns

    ctx = LocalSparkContext("local-cluster[1,1,1024]", "wedge-midrun-test")
    try:
        t0 = time.monotonic()
        with pytest.raises(RuntimeError) as ei:
            cluster = TFCluster.run(
                sc=ctx, map_fun=wedged_train_fun, tf_args=None,
                num_executors=1,
                input_mode=TFCluster.InputMode.TENSORFLOW,
            )
            cluster.shutdown(grace_secs=60)
        msg = str(ei.value)
        # the watchdog's report_error reached the driver's exception: the
        # sick executor names itself and the stall reason
        assert "stalled" in msg and "executor 0" in msg, msg
        assert time.monotonic() - t0 < 90
    finally:
        ctx.stop()


def test_train_requires_spark_mode(sc):
    cluster = TFCluster.run(sc, tf_mode_fun, tf_args=None, num_executors=2,
                            input_mode=TFCluster.InputMode.TENSORFLOW)
    with pytest.raises(RuntimeError, match="InputMode.SPARK"):
        cluster.train(sc.parallelize([1], 1))
    cluster.shutdown(grace_secs=30)


def ckpt_train_fun(args, ctx):
    """Trainer-based map_fun exercising the restart-from-checkpoint model
    (SURVEY §5: fail fast, resume from the last checkpoint)."""
    from tensorflowonspark_tpu import util

    util.ensure_jax_platform()
    import numpy as np

    from tensorflowonspark_tpu.trainer import Trainer

    t = Trainer("mnist_mlp", learning_rate=1e-2)
    if args.restore:
        t.restore(args.model_dir)
    feed = ctx.get_data_feed(train_mode=True, input_mapping=["image", "label"])
    while not feed.should_stop():
        batch = feed.next_batch(32)
        if not batch or batch["image"].shape[0] == 0:
            continue
        t.step({"image": np.asarray(batch["image"], np.float32),
                "label": np.asarray(batch["label"], np.int32)})
    ctx.mgr.set("step_count", int(t.state.step))
    if ctx.job_name == "chief":
        t.save(args.model_dir)


def test_checkpoint_restart_through_cluster(sc, tmp_path):
    """Job 1 trains and checkpoints; job 2 restores and CONTINUES — the
    step counter carries across cluster restarts (the documented recovery
    model: spark.task.maxFailures=1 + restart from checkpoint)."""
    import argparse

    rng = np.random.default_rng(0)
    data = [(rng.random(64).astype(np.float32), int(i % 10))
            for i in range(256)]
    model_dir = str(tmp_path / "ckpt")

    def run_job(restore):
        args = argparse.Namespace(model_dir=model_dir, restore=restore)
        cluster = TFCluster.run(sc, ckpt_train_fun, tf_args=args,
                                num_executors=2, master_node="chief",
                                input_mode=TFCluster.InputMode.SPARK)
        cluster.train(sc.parallelize(data, 2), num_epochs=2,
                      feed_timeout=120)
        cluster.shutdown(grace_secs=30)
        authkey = bytes.fromhex(cluster.cluster_meta["authkey_hex"])
        return {
            meta["job_name"]: TFManager.connect(
                tuple(meta["addr"]), authkey).get("step_count")
            for meta in cluster.cluster_info
        }

    first = run_job(restore=False)
    assert all(s and s > 0 for s in first.values()), first
    second = run_job(restore=True)
    # every node restored the chief's checkpoint: its counter continues
    # from the first job's chief step count instead of restarting at zero
    for job, steps in second.items():
        assert steps > first["chief"], (first, second)


def test_report_error_attribution_survives_manager_reaping():
    """ctx.report_error mirrors the attribution onto the DRIVER-side
    rendezvous kv: the node's own error queue dies with its manager (~15s
    orphan-watch fuse), but the driver can still recover the watchdog's
    last words minutes later (the round-4 review's evidence-TTL race)."""
    import threading as _threading

    from tensorflowonspark_tpu import reservation
    from tensorflowonspark_tpu.TFSparkNode import TFNodeContext

    server = reservation.Server(1)
    addr = server.start()
    mgr = TFManager.start(b"k", ["input", "output", "error"])
    try:
        ctx = TFNodeContext(
            executor_id=0, job_name="worker", task_index=0,
            cluster_spec={}, default_fs="file://", working_dir=".",
            mgr_addr=mgr.address, authkey=b"k", cluster_info=[],
            cluster_id="c1", server_addr=addr,
            auth_token=server.auth_token)
        ctx.report_error("train step stalled for 45s (watchdog)")
        ctx.report_error("second incident")
        # queue copy (the fast path) is present while the manager lives
        assert "stalled" in mgr.get_queue("error").get(timeout=5)
        # durable copies on the rendezvous kv, enumerable by the driver
        items = server.kv_items("node_error:")
        assert list(items) == ["node_error:worker:0"]
        assert len(items["node_error:worker:0"]) == 2
        assert "stalled" in items["node_error:worker:0"][0]
    finally:
        mgr.shutdown()  # the orphan-watch fate, accelerated

    # driver-side drain recovers the attribution with the manager gone
    cluster = TFCluster.TFCluster(
        sc=None,
        cluster_meta={"authkey_hex": "00" * 16, "num_executors": 0},
        cluster_info=[], server=server,
        input_mode=TFCluster.InputMode.SPARK,
        bootstrap_thread=_threading.Thread(target=lambda: None))
    drained = cluster._drain_node_errors()
    assert any("stalled" in m for m in drained)
    # idempotent: a second drain returns the cache, no duplicates
    assert cluster._drain_node_errors() == drained
    server.stop()
