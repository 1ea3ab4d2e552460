"""Pipeline flight recorder (obs.flight): stage attribution + verdicts.

The acceptance core: each bottleneck verdict class is INDUCED through the
real data-plane code paths — feed-starved via a throttled feeder into a
live TFManager queue, device-bound via a slow fake forward through
``pipeline._RunModel``, emit-bound via a slow consumer of the same — and
the classifier must name it.  Plus the recorder mechanics (overlap
accounting, sampling, opt-out, breakdown reconciliation) and the
driver-side rendering behind ``/pipeline`` and ``check_anomalies()``.
"""

import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from tensorflowonspark_tpu import TFManager, compat, marker, obs, shm  # noqa: E402
from tensorflowonspark_tpu.TFNode import DataFeed  # noqa: E402
from tensorflowonspark_tpu.obs import flight  # noqa: E402


# -- classifier --------------------------------------------------------------


def test_classify_names_each_verdict_class():
    assert flight.classify({"wait": 0.9, "compute": 0.05}) == "feed_starved"
    assert flight.classify({"compute": 0.8, "wait": 0.1}) == "device_bound"
    assert flight.classify({"emit": 0.7, "compute": 0.1,
                            "wait": 0.1}) == "emit_bound"
    assert flight.classify(
        {"backpressure": 0.9, "encode": 0.05}) == "queue_backpressured"
    assert flight.classify({"ingest": 0.5, "pad": 0.2, "stage": 0.2,
                            "compute": 0.1}) == "ingest_bound"


def test_classify_balanced_and_edge_cases():
    # no dominant category
    assert flight.classify({"wait": 0.4, "compute": 0.4,
                            "emit": 0.2}) == "balanced"
    # empty / all-zero records
    assert flight.classify({}) == "balanced"
    assert flight.classify({"wait": 0.0}) == "balanced"
    # overlapped (_bg) and unknown stages never classify
    assert flight.classify({"ingest_bg": 9.0, "compute": 0.1,
                            "mystery": 5.0}) == "device_bound"


# -- recorder mechanics ------------------------------------------------------


def test_recorder_overlap_accounting_and_breakdown():
    rec = flight.FlightRecorder("unit")
    rec.add(wait=0.2, compute=0.7)
    rec.add(overlapped=True, ingest=0.5)  # pump work: not critical path
    assert rec.commit() == "device_bound"
    bd = rec.breakdown(wall_s=1.0)
    assert bd["stage_sum_s"] == pytest.approx(0.9)
    assert bd["stage_sum_frac"] == pytest.approx(0.9)
    assert bd["overlapped_stages_s"] == {"ingest": 0.5}
    assert bd["verdict"] == "device_bound"
    assert bd["batches"] == 1
    rec.reset()
    assert rec.batches == 0 and rec.totals() == {}


def test_recorder_disabled_by_env(monkeypatch):
    monkeypatch.setenv("TFOS_FLIGHT", "0")
    rec = flight.FlightRecorder("unit_off")
    rec.add(wait=1.0)
    assert rec.commit() is None
    assert rec.batches == 0
    monkeypatch.setenv("TFOS_FLIGHT", "1")
    rec.add(wait=1.0)
    assert rec.commit() == "feed_starved"


def test_sampling_knob_thins_histograms_not_verdicts(monkeypatch):
    monkeypatch.setattr(flight, "SAMPLE_EVERY", 3)
    rec = flight.FlightRecorder("unit_sampled")
    for _ in range(9):
        rec.add(compute=0.01)
        rec.commit()
    # verdict counting stays exact
    assert rec.batches == 9
    assert rec.verdict() == "device_bound"
    reg = obs.get_registry().snapshot()
    assert reg["counters"]["flight_unit_sampled_verdict_device_bound_total"] \
        == 9
    # histograms thinned to ~every 3rd batch
    h = reg["histograms"]["flight_unit_sampled_compute_seconds"]
    assert 1 <= h["count"] < 9


def test_recorder_registry_is_per_plane_singleton():
    assert flight.recorder("feed") is flight.recorder("feed")
    assert flight.recorder("feed") is not flight.recorder("serve")


# -- verdict induction through the REAL paths --------------------------------


def _rows(n, dim=8):
    rng = np.random.default_rng(3)
    feats = rng.standard_normal((n, dim)).astype(np.float32)
    return feats, [(feats[i], i) for i in range(n)]


def test_feed_starved_verdict_via_throttled_feeder():
    """A feeder that trickles chunks into a live TFManager queue starves
    the consumer: the DataFeed's queue-blocked `wait` dominates the step
    and the committed verdicts say feed_starved."""
    _, rows = _rows(64)
    rec = flight.recorder("feed")
    rec.reset()
    m = TFManager.start(b"flight-feed", ["input", "output", "error"],
                        mode="local")
    try:
        q = m.get_queue("input")

        def feeder():
            for i in range(0, 64, 16):
                time.sleep(0.05)  # the throttle
                q.put(shm.encode_chunk(rows[i:i + 16], transport="pickle"))
            q.put(marker.StopFeed())

        feed = DataFeed(m, input_mapping=["x", "y"])
        th = threading.Thread(target=feeder, daemon=True)
        th.start()
        n = 0
        while not feed.should_stop():
            batch = feed.next_batch(16)
            if batch:
                n += int(batch["y"].shape[0])
            rec.add(compute=0.0002)  # a fast fake trainer step
            rec.commit()
        th.join(timeout=30)
    finally:
        m.shutdown()
    assert n == 64
    assert rec.batches >= 4
    assert rec.verdict() == "feed_starved"
    bd = rec.breakdown(1.0)
    assert bd["stages_s"]["wait"] > 10 * bd["stages_s"].get("compute", 0.0)


@pytest.fixture()
def linear_export(tmp_path):
    """A tiny linear export + the Row partitions to score through the
    real ``_RunModel`` serving plane."""
    from tensorflowonspark_tpu.sparkapi.sql import Row

    rng = np.random.default_rng(0)
    w = rng.standard_normal((8, 3)).astype(np.float32)
    feats, _ = _rows(96)
    export_dir = str(tmp_path / "export")
    compat.export_saved_model({"params": {"w": w}}, export_dir)
    rows = [Row.from_fields(["features", "id"], [feats[i], i])
            for i in range(96)]
    return export_dir, w, rows


def _run_model(export_dir, predict_fn, batch_size=32):
    from tensorflowonspark_tpu import pipeline

    return pipeline._RunModel(
        export_dir=export_dir, model_name=None, predict_fn=predict_fn,
        batch_size=batch_size, input_mapping={"features": "features"},
        output_mapping={"score": "score"}, columns=["features", "id"],
        backend="sparkapi")


def test_device_bound_verdict_via_slow_fake_forward(linear_export):
    """A slow forward through the real serving plane: `compute` dominates
    every batch and the verdict is device_bound."""
    export_dir, w, rows = linear_export

    def slow_forward(params, batch):
        time.sleep(0.03)  # the fake device
        return {"score": np.asarray(batch["features"]) @ params["w"]}

    rm = _run_model(export_dir, slow_forward)
    list(rm(iter(rows)))  # warm the model cache: load time is one-off,
    # spanned as serving.model_load, and not part of per-batch attribution
    rec = flight.recorder("serve")
    rec.reset()
    t0 = time.perf_counter()
    out = list(rm(iter(rows)))
    wall = time.perf_counter() - t0
    assert len(out) == 96
    assert rec.batches >= 3
    assert rec.verdict() == "device_bound"
    # the additive consumer stages reconcile with the measured wall — the
    # property the bench gate enforces on every artifact
    bd = rec.breakdown(wall)
    assert 0.8 <= bd["stage_sum_frac"] <= 1.2, bd


def test_depth_zero_breakdown_still_reconciles(linear_export, monkeypatch):
    """TFOS_SERVING_PREFETCH=0 runs the pump inline inside the consumer's
    next(): the ingest/pad/stage window must then count ONCE (as additive
    stages, not also as consumer wait) or the stage sum runs toward 2x
    wall and the gate fails a healthy synchronous run."""
    monkeypatch.setenv("TFOS_SERVING_PREFETCH", "0")
    export_dir, w, rows = linear_export

    def forward(params, batch):
        time.sleep(0.005)
        return {"score": np.asarray(batch["features"]) @ params["w"]}

    rm = _run_model(export_dir, forward)
    list(rm(iter(rows)))  # warm the model cache
    rec = flight.recorder("serve")
    rec.reset()
    t0 = time.perf_counter()
    assert len(list(rm(iter(rows)))) == 96
    wall = time.perf_counter() - t0
    bd = rec.breakdown(wall)
    assert 0.8 <= bd["stage_sum_frac"] <= 1.2, bd
    # the pump stages counted as additive (nothing overlapped at depth 0)
    assert bd["overlapped_stages_s"] == {}
    assert "ingest" in bd["stages_s"] and "wait" not in bd["stages_s"]


def test_emit_bound_verdict_via_slow_consumer(linear_export):
    """A fast forward with a slow downstream consumer: the generator
    suspension lands in `emit` and the verdict says so — the serving
    plane is healthy, the caller isn't keeping up."""
    import jax

    export_dir, w, rows = linear_export
    fast = jax.jit(lambda p, b: {"score": b["features"] @ p["w"]})
    rm = _run_model(export_dir, fast)
    list(rm(iter(rows)))  # warm: jit compile must not count as compute
    rec = flight.recorder("serve")
    rec.reset()
    n = 0
    for _row in rm(iter(rows)):
        time.sleep(0.002)  # the slow consumer
        n += 1
    assert n == 96
    assert rec.batches >= 3
    assert rec.verdict() == "emit_bound", rec.snapshot()


# -- driver-side rendering ---------------------------------------------------


def _starved_registry(starved=30, device=5):
    reg = obs.Registry()
    c = reg.counter("flight_feed_verdict_feed_starved_total")
    for _ in range(starved):
        c.inc()
    d = reg.counter("flight_feed_verdict_device_bound_total")
    for _ in range(device):
        d.inc()
    reg.counter("flight_feed_batches_total").inc(starved + device)
    for _ in range(10):
        reg.histogram("flight_feed_wait_seconds").observe(0.08)
        reg.histogram("flight_feed_compute_seconds").observe(0.004)
    return reg.snapshot()


def test_report_from_metrics_renders_per_node_planes():
    agg = {"nodes": {"worker:0": {"registry": _starved_registry()},
                     "worker:1": {"registry": {}}}}
    report = flight.report_from_metrics(agg)
    feed = report["planes"]["feed"]
    node = feed["nodes"]["worker:0"]
    assert node["batches"] == 35
    assert node["verdict"] == "feed_starved"
    assert node["stages"]["wait"]["p50"] > node["stages"]["compute"]["p50"]
    assert feed["verdicts"] == {"feed_starved": 30, "device_bound": 5}
    assert feed["verdict"] == "feed_starved"


def test_detect_feed_starvation_finding_carries_evidence():
    agg = {"nodes": {"worker:0": {"registry": _starved_registry()}}}
    findings = flight.detect_feed_starvation(agg)
    assert len(findings) == 1
    f = findings[0]
    assert f["node"] == "worker:0" and f["plane"] == "feed"
    assert f["ratio"] == pytest.approx(30 / 35, abs=1e-3)
    assert f["batches"] == 35
    assert f["wait_p50_s"] > 0  # the evidence: where the time goes
    # a mostly-healthy node is not a finding
    healthy = {"nodes": {"worker:0": {
        "registry": _starved_registry(starved=5, device=30)}}}
    assert flight.detect_feed_starvation(healthy) == []
    # too few classified batches is not a finding (cold start)
    cold = {"nodes": {"worker:0": {
        "registry": _starved_registry(starved=5, device=0)}}}
    assert flight.detect_feed_starvation(cold) == []


# -- bench integration -------------------------------------------------------


def test_feed_transport_breakdown_reconciles_and_stamps_overhead():
    """The stamped ``feed_stage_breakdown`` must explain the measured wall
    (the gate's reconciliation contract) and carry the feeder split +
    measured recorder overhead."""
    import bench

    out = bench.measure_feed_transport(rows_total=256, chunk_rows=64,
                                       batch_size=128, feature_dim=256)
    bd = out["feed_stage_breakdown"]
    assert bd["verdict"] in flight.VERDICTS
    assert bd["batches"] >= 2
    assert 0.8 <= bd["stage_sum_frac"] <= 1.2, bd
    assert set(bd["stages_s"]) >= {"wait", "ingest"}
    assert "encode" in bd["feeder_stages_s"]
    if shm.shm_available():
        assert isinstance(out["feed_flight_overhead_frac"], float)


@pytest.mark.slow
def test_flight_recorder_overhead_under_3_percent(tmp_path, monkeypatch):
    """Acceptance: recorder on vs TFOS_FLIGHT=0 degrades rows/sec < 3% on
    the PR 3 (feed transport) and PR 5 (serving) bench paths.

    Feed: the bench's own stamped A/B (multi-second passes — ambient
    noise well under the margin).  Serving: a direct alternated A/B over
    the real ``_RunModel`` path at ``TFOS_SERVING_PREFETCH=0`` — with the
    pump thread on, 2-core scheduler bimodality swings rep walls ±3x and
    drowns a 3% signal in either direction (bench stamps that honest
    macro number anyway); at depth 0 the pass is deterministic and the
    recorder's per-batch add/commit work — the thing being measured — is
    identical code.  Slow-marked: minutes of wall-clock timing loops."""
    import bench
    import jax

    from tensorflowonspark_tpu import compat, pipeline

    # each call's stamp is already an order-alternated best-of-2 vs
    # best-of-2 A/B; best-of-2 calls rides out ambient load spikes
    fracs = [bench.measure_feed_transport(
        rows_total=2048, chunk_rows=256, batch_size=1024,
        feature_dim=8192)["feed_flight_overhead_frac"] for _ in range(2)]
    assert min(fracs) < 0.03, fracs

    monkeypatch.setenv("TFOS_SERVING_PREFETCH", "0")
    from tensorflowonspark_tpu.sparkapi.sql import Row

    rng = np.random.default_rng(0)
    n_rows = 32768
    w = rng.standard_normal((256, 8)).astype(np.float32)
    feats = rng.standard_normal((n_rows, 256)).astype(np.float32)
    rows = [Row.from_fields(["features", "id"], [feats[i], i])
            for i in range(n_rows)]
    parts = [rows[i:i + 4128] for i in range(0, n_rows, 4128)]
    export_dir = str(tmp_path / "export")
    compat.export_saved_model({"params": {"w": w}}, export_dir)
    predict = jax.jit(lambda p, b: {"score": b["features"] @ p["w"]})
    rm = pipeline._RunModel(
        export_dir=export_dir, model_name=None, predict_fn=predict,
        batch_size=1024, input_mapping={"features": "features"},
        output_mapping={"score": "score"}, columns=["features", "id"],
        backend="sparkapi", bucket_sizes=[256, 1024])

    def drive() -> float:
        t0 = time.perf_counter()
        n = 0
        for part in parts:
            n += len(list(rm(iter(part))))
        assert n == n_rows
        return time.perf_counter() - t0

    drive()
    drive()  # warm: model cache + jit + allocator
    on, off = [], []
    for i in range(16):
        # alternate order within each pair: GC/cache position effects hit
        # both modes symmetrically
        order = (("1", on), ("0", off)) if i % 2 == 0 else \
            (("0", off), ("1", on))
        for mode, acc in order:
            monkeypatch.setenv("TFOS_FLIGHT", mode)
            acc.append(drive())

    def floor(dts):  # trimmed floor: single fastest samples still jitter
        return sum(sorted(dts)[:4]) / 4

    overhead = floor(on) / floor(off) - 1.0
    assert overhead < 0.03, (overhead, sorted(on)[:5], sorted(off)[:5])


def test_bench_stamps_null_breakdown_when_recorder_disabled(monkeypatch):
    """The documented TFOS_FLIGHT=0 opt-out must not produce a zero-sum
    breakdown the gate would fail: the bench stamps explicit null +
    reason instead, and skips the meaningless overhead A/B."""
    import bench

    monkeypatch.setenv("TFOS_FLIGHT", "0")
    out = bench.measure_feed_transport(rows_total=128, chunk_rows=64,
                                       batch_size=64, feature_dim=32)
    assert out["feed_stage_breakdown"] is None
    assert "TFOS_FLIGHT=0" in out["feed_stage_breakdown_reason"]
    assert "feed_flight_overhead_frac" not in out
