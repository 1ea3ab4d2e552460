"""Observability subsystem unit tests (ISSUE 1 tentpole): span
nesting/ordering, ring-buffer bounds, blackboard shipping, Chrome-trace
merge determinism, registry semantics, and Prometheus exposition."""

import json
import os
import threading
import time

import pytest

from tensorflowonspark_tpu import obs
from tensorflowonspark_tpu.obs import chrome, registry as reg
from tensorflowonspark_tpu.obs.trace import Tracer


# ---------------------------------------------------------------------------
# tracing: spans + events + ring buffer
# ---------------------------------------------------------------------------


def test_span_nesting_and_ordering():
    tr = Tracer(node="t")
    with tr.span("outer", phase="reserve"):
        with tr.span("inner"):
            time.sleep(0.002)
        tr.event("mark", k=1)
    evs = tr.snapshot()
    names = [e["name"] for e in evs]
    # completion order: inner closes before outer; the instant lands between
    assert names == ["inner", "mark", "outer"]
    inner, mark, outer = evs
    assert inner["attrs"]["parent"] == "outer"
    assert "parent" not in (outer.get("attrs") or {})
    assert outer["attrs"]["phase"] == "reserve"
    assert mark["ph"] == "i" and mark["attrs"] == {"k": 1, "parent": "outer"}
    # the outer span contains the inner span on the timeline
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]


def test_span_decorator_and_error_capture():
    tr = Tracer(node="t")

    @tr.span("work", kind="decorated")
    def work(x):
        return x * 2

    assert work(21) == 42
    with pytest.raises(ValueError):
        with tr.span("failing"):
            raise ValueError("boom")
    evs = {e["name"]: e for e in tr.snapshot()}
    assert evs["work"]["attrs"]["kind"] == "decorated"
    assert "ValueError: boom" in evs["failing"]["attrs"]["error"]


def test_span_set_attaches_outcome():
    tr = Tracer(node="t")
    with tr.span("probe", timeout_s=5) as sp:
        sp.set(ok=False, reason="hung")
    ev = tr.snapshot()[0]
    assert ev["attrs"] == {"timeout_s": 5, "ok": False, "reason": "hung"}


def test_ring_buffer_bounds_memory_and_counts_drops():
    tr = Tracer(node="t", capacity=10)
    for i in range(25):
        tr.event(f"e{i}")
    evs = tr.snapshot()
    assert len(evs) == 10
    assert tr.dropped == 15
    assert evs[0]["name"] == "e15"  # oldest evicted first


def test_tracer_disabled_by_env(monkeypatch):
    monkeypatch.setenv("TFOS_TRACE", "0")
    tr = Tracer(node="t")
    tr.event("never")
    with tr.span("also-never"):
        pass
    assert tr.snapshot() == []


def test_threaded_spans_do_not_cross_nest():
    """Each thread keeps its own span stack: a span opened in thread A must
    not become the parent of a span in thread B."""
    tr = Tracer(node="t")
    ready = threading.Event()

    def other():
        ready.wait(5)
        with tr.span("b"):
            pass

    th = threading.Thread(target=other)
    th.start()
    with tr.span("a"):
        ready.set()
        th.join(5)
    evs = {e["name"]: e for e in tr.snapshot()}
    assert "parent" not in (evs["b"].get("attrs") or {})


def test_span_feeds_flight_stage_and_duration_from_one_clock_pair():
    """The site reads no clock of its own: the span's duration is the
    flight stage's and ``dur_s``."""
    tr = Tracer(node="t")
    rec = obs.flight.FlightRecorder("spantest")
    with tr.span("feed.collate").flight(rec, "collate", True) as sp:
        time.sleep(0.01)
    (ev,) = tr.snapshot()
    assert sp.dur_s == pytest.approx(ev["dur"] * 1e-6)
    assert rec.totals_overlapped() == {"collate": pytest.approx(sp.dur_s)}
    with tr.span("reader.batch") as sp:        # nothing to read: leave out
        sp.cancel()
    assert len(tr.snapshot()) == 1


def test_span_times_its_stretch_with_recording_off(monkeypatch):
    """``TFOS_TRACE=0``: nothing recorded, but the flight stage and the
    goodput ledger still get their durations from the span."""
    monkeypatch.setenv("TFOS_TRACE", "0")
    tr = Tracer(node="t")
    rec = obs.flight.FlightRecorder("spantest_off")
    with tr.span("trainer.shard").flight(rec, "shard") as sp:
        time.sleep(0.005)
    assert tr.snapshot() == [] and sp.trace_id is None
    assert sp.dur_s >= 0.005 and rec.totals() == {"shard": sp.dur_s}


def test_root_span_starts_a_trace_of_its_own_and_complete_nests():
    tr = Tracer(node="t")
    with tr.span("node.map_fun") as outer:
        with tr.span("trainer.step", step=1).root() as step:
            with tr.span("trainer.dispatch"):
                pass
        tr.complete("feed.turnround", time.time() - 1.0, 1.0, rows=3)
    evs = {e["name"]: e for e in tr.snapshot()}
    assert step.trace_id != outer.trace_id
    assert evs["trainer.dispatch"]["trace_id"] == step.trace_id
    assert evs["trainer.dispatch"]["parent_span_id"] == \
        evs["trainer.step"]["span_id"]
    assert "parent_span_id" not in evs["trainer.step"]
    turn = evs["feed.turnround"]
    assert turn["dur"] == pytest.approx(1e6) and turn["attrs"] == {
        "rows": 3, "parent": "node.map_fun"}
    assert turn["parent_span_id"] == evs["node.map_fun"]["span_id"]


def test_span_is_in_the_profile_and_in_the_ring_with_the_same_step(tmp_path):
    """In a process that has imported JAX a span is also a
    ``TraceAnnotation``: a profiler session holds it on the host plane, on
    its own clock, with the ring's ``step``; the pairs give the offset
    between the clocks."""
    import glob

    import jax
    from jax.profiler import ProfileData

    tr = Tracer(node="t")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for step in (7, 8, 9):
            with tr.span("trainer.step", step=step):
                with tr.span("trainer.dispatch"):
                    time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    profile = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in ("trainer.step", "trainer.dispatch"):
                        profile.setdefault(ev.name, []).append(
                            (dict(ev.stats).get("step"), ev.start_ns * 1e-9,
                             ev.duration_ns * 1e-9))
    ring = [e for e in tr.snapshot() if e["name"] == "trainer.step"]
    assert [s for s, _, _ in profile["trainer.step"]] == [7, 8, 9] == [
        e["attrs"]["step"] for e in ring]
    assert len(profile["trainer.dispatch"]) == 3
    for (_, _, dur), ev in zip(profile["trainer.step"], ring):
        assert dur == pytest.approx(ev["dur"] * 1e-6, abs=1e-3)
    clock = obs.clock_offset([(ev["ts"] * 1e-6, start) for (_, start, _), ev
                              in zip(profile["trainer.step"], ring)])
    assert clock["pairs"] == 3 and clock["spread_s"] < 1e-3


def test_clock_offset_places_another_process_within_the_pair_spread():
    true_offset = 4321.25
    noise = [0.0003, -0.0002, 0.0005, -0.0004, 0.0001, 0.0, 0.0002]
    pairs = [(1000.0 + i, 1000.0 + i + true_offset + n)
             for i, n in enumerate(noise)]
    clock = obs.clock_offset(pairs)
    assert clock["pairs"] == 7 and 0 < clock["spread_s"] < 1e-3
    feeder_start = 1003.5           # another process, the same wall clock
    placed = feeder_start + clock["offset_s"]
    assert abs(placed - (feeder_start + true_offset)) <= clock["spread_s"]
    assert obs.clock_offset([]) is None


def _no_jax_script(tmp_path, body):
    import subprocess
    import sys

    import tensorflowonspark_tpu

    repo = os.path.dirname(os.path.dirname(tensorflowonspark_tpu.__file__))
    script = tmp_path / "no_jax.py"
    script.write_text(
        "import json, os, sys, threading\n"
        f"sys.path.insert(0, {repo!r})\n"
        "def main():\n" + "".join(
            f"    {line}\n" for line in body.strip().splitlines())
        + "    bad = [m for m in sys.modules if m == 'jax' or "
        "m.startswith(('jax.', 'jaxlib'))]\n"
        "    assert not bad, bad\n"
        "if __name__ == '__main__':\n    main()\n")
    proc = subprocess.run([sys.executable, str(script)], capture_output=True,
                          text=True, timeout=120, cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_a_process_without_jax_imports_none_for_a_span(tmp_path):
    out = _no_jax_script(tmp_path, """
from tensorflowonspark_tpu import obs
with obs.span("cluster.reserve", step=3):
    with obs.span("feeder.task"):
        pass
obs.complete("feed.turnround", 1.0, 0.5)
print(json.dumps([e["name"] for e in obs.get_tracer().snapshot()]))
""")
    assert json.loads(out.strip().splitlines()[-1]) == [
        "feeder.task", "cluster.reserve", "feed.turnround"]


def test_the_executors_feeder_task_stays_off_jax(tmp_path):
    """A partition fed through ``TFSparkNode.train`` in a process of its
    own: the feeder's spans are recorded, JAX is never imported."""
    out = _no_jax_script(tmp_path, """
from tensorflowonspark_tpu import TFManager, TFSparkNode, marker, obs, shm, util
key = b"feeder-no-jax"
mgr = TFManager.start(key, ["input", "output", "error"], mode="local")
mgr.set("state", "running")
util.write_executor_id(0, name=TFSparkNode._guard_name("cid"))
info = [{"executor_id": 0, "addr": list(mgr.address), "job_name": "worker",
         "task_index": 0}]
meta = {"id": "cid", "authkey_hex": key.hex(), "feed_chunk": 4}
q = mgr.get_queue("input")
def drain():
    while not isinstance(item := q.get(), marker.EndPartition):
        shm.maybe_unlink_payload(item)
t = threading.Thread(target=drain)
t.start()
TFSparkNode.train(info, meta, 30.0, "input")(
    iter([(float(i), i) for i in range(10)]))
t.join()
spans = {e["name"]: e for e in obs.get_tracer().snapshot()}
print(json.dumps({k: v.get("attrs") for k, v in spans.items()}))
mgr.shutdown()
""")
    spans = json.loads(out.strip().splitlines()[-1])
    assert set(spans) == {"feeder.task", "feeder.connect", "feeder.first_row",
                          "feeder.send", "feeder.drain_wait"}
    send = spans["feeder.send"]
    assert send["chunks"] == 3 and send["rows"] == 10 and send["bytes"] > 0
    assert send["encode_s"] > 0 and send["parent"] == "feeder.task"


# ---------------------------------------------------------------------------
# executor→driver shipping through the (fake) kv blackboard
# ---------------------------------------------------------------------------


class FakeMgr:
    def __init__(self):
        self.kv = {}

    def set(self, k, v):
        self.kv[k] = v

    def kv_snapshot(self):
        return dict(self.kv)


def test_flush_ships_snapshot_to_own_kv_key():
    tr = Tracer(node="worker:0")
    mgr = FakeMgr()
    with tr.span("node.bootstrap"):
        pass
    assert tr.flush(mgr)
    (key,) = mgr.kv.keys()
    assert key.startswith(obs.TRACE_KV_PREFIX + "worker:0:")
    payload = mgr.kv[key]
    assert payload["node"] == "worker:0"
    assert [e["name"] for e in payload["events"]] == ["node.bootstrap"]


def test_flush_survives_broken_mgr():
    class Broken:
        def set(self, k, v):
            raise ConnectionError("gone")

    tr = Tracer(node="worker:0")
    tr.event("x")
    assert tr.flush(Broken()) is False  # must not raise


def test_recording_never_ships_the_shipper_thread_does():
    """``record`` appends and returns: no manager call on the recording
    thread, however many events.  The daemon ships what came since its
    cursor, as a chunk under a key of its own."""
    calls = []

    class ThreadMgr(FakeMgr):
        def set(self, k, v):
            calls.append(threading.current_thread().name)
            super().set(k, v)

    tr = Tracer(node="worker:0")
    mgr = ThreadMgr()
    tr.flush_interval_s = 0.05
    tr.configure(mgr=mgr)
    for i in range(200):
        tr.event(f"e{i}")
    deadline = time.time() + 5
    while time.time() < deadline and sum(
            len(p["events"]) for p in mgr.kv.values()) < 200:
        time.sleep(0.02)
    assert calls and set(calls) == {"tfos-trace-shipper"}
    shipped = obs.collect_blackboard(mgr.kv_snapshot())["worker:0"]
    assert sorted(e["name"] for e in shipped) == sorted(
        f"e{i}" for i in range(200))
    tr.clear()      # takes the manager away: the shipper thread ends
    tr._shipper.join(5)
    assert not tr._shipper.is_alive()


def test_incremental_shipping_loses_and_repeats_no_event():
    """A recorder thread records while the shipper and explicit flushes
    ship: every event reaches the blackboard exactly once, in chunks that
    do not overlap."""
    tr = Tracer(node="worker:0")
    mgr = FakeMgr()
    tr.flush_interval_s = 0.001
    tr.configure(mgr=mgr)
    n = 5000

    def recorder():
        for i in range(n):
            tr.event("e", i=i)

    th = threading.Thread(target=recorder)
    th.start()
    while th.is_alive():
        tr.flush()
    th.join()
    assert tr.flush()
    chunks = list(mgr.kv.values())
    assert len(chunks) > 1
    seen = [e["attrs"]["i"] for p in chunks for e in p["events"]]
    assert sorted(seen) == list(range(n))       # none lost, none repeated
    assert all(p["dropped"] == 0 for p in chunks)
    assert obs.collect_dropped(mgr.kv_snapshot()) == {"worker:0": 0}
    tr.clear()


def test_shipping_bounds_the_blackboard_and_counts_what_it_dropped():
    class Mgr(FakeMgr):
        def delete(self, k):
            self.kv.pop(k, None)

    tr = Tracer(node="w", capacity=10)
    mgr = Mgr()
    for i in range(25):         # 15 fall out of the ring before any flush
        tr.event(f"e{i}")
    assert tr.flush(mgr)
    assert obs.collect_dropped(mgr.kv_snapshot()) == {"w": 15}
    for i in range(25, 31):     # 10 + 6 > capacity: the old chunk goes
        tr.event(f"e{i}")
    assert tr.flush(mgr)
    names = [e["name"] for e in obs.collect_blackboard(
        mgr.kv_snapshot())["w"]]
    assert names == [f"e{i}" for i in range(25, 31)]
    assert obs.collect_dropped(mgr.kv_snapshot()) == {"w": 25}


def test_flush_publishes_the_registry_snapshot(monkeypatch):
    mgr = FakeMgr()
    monkeypatch.setattr(obs.trace._TRACER, "node", "worker:3")
    obs.counter("flush_publishes_total").inc(4)
    assert obs.flush(mgr)
    counters = obs.collect_counters(mgr.kv_snapshot())
    (key,) = counters
    assert key.startswith("worker:3:")
    assert counters[key]["counters"]["flush_publishes_total"] == 4


def test_collect_blackboard_merges_processes_of_one_node():
    """A node has two publishing processes (bootstrap task + spawned
    trainer): their events merge under one node name, time-ordered."""
    t1 = Tracer(node="worker:0")
    t2 = Tracer(node="worker:0")
    mgr = FakeMgr()
    t1.event("bootstrap.early")
    time.sleep(0.002)
    t2.event("trainer.late")
    t1.flush(mgr)
    # fake a distinct pid for the second process's key
    payload = {"node": "worker:0", "pid": 99999, "events": t2.snapshot(),
               "dropped": 0, "flushed_at": time.time()}
    mgr.set(f"{obs.TRACE_KV_PREFIX}worker:0:99999", payload)
    by_node = obs.collect_blackboard(mgr.kv_snapshot())
    assert list(by_node) == ["worker:0"]
    assert [e["name"] for e in by_node["worker:0"]] == [
        "bootstrap.early", "trainer.late"]


def test_collect_blackboard_ignores_non_trace_keys():
    kv = {"metrics": {"step": 3}, "state": "running",
          "trace:w:1": {"node": "w", "events": [
              {"name": "a", "ph": "i", "ts": 1.0, "pid": 1, "tid": 1}]},
          "trace:junk": "not-a-payload"}
    by_node = obs.collect_blackboard(kv)
    assert list(by_node) == ["w"]


# ---------------------------------------------------------------------------
# Chrome trace merge
# ---------------------------------------------------------------------------


def _mk_event(name, ts, node="n", ph="X", dur=5.0, tid=1):
    ev = {"name": name, "ph": ph, "ts": ts, "node": node, "pid": 1,
          "tid": tid}
    if ph == "X":
        ev["dur"] = dur
    return ev


def test_chrome_merge_is_deterministic_and_stable(tmp_path):
    by_node = {
        "worker:1": [_mk_event("b", 200.0), _mk_event("a", 100.0)],
        "driver": [_mk_event("run", 50.0, dur=500.0)],
        "worker:0": [_mk_event("c", 150.0, ph="i")],
    }
    p1, p2 = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    chrome.write(p1, by_node)
    # same logical input, different dict insertion order → identical bytes
    shuffled = {k: list(reversed(v)) for k, v in reversed(by_node.items())}
    chrome.write(p2, shuffled)
    assert open(p1, "rb").read() == open(p2, "rb").read()

    doc = json.load(open(p1))
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    rows = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    # driver gets pid 1 (first track); workers follow sorted
    names_by_pid = {m["pid"]: m["args"]["name"] for m in meta}
    assert names_by_pid == {1: "driver", 2: "worker:0", 3: "worker:1"}
    # events globally time-ordered
    assert [r["ts"] for r in rows] == sorted(r["ts"] for r in rows)
    # instant events carry scope, complete events carry dur
    assert all("dur" in r for r in rows if r["ph"] == "X")
    assert all(r.get("s") == "t" for r in rows if r["ph"] == "i")


def test_chrome_merge_skips_malformed_phases():
    doc = chrome.merge({"n": [_mk_event("ok", 1.0),
                              _mk_event("bad", 2.0, ph="Z")]})
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] != "M"]
    assert names == ["ok"]


# ---------------------------------------------------------------------------
# registry: counters / gauges / histograms, Prometheus exposition
# ---------------------------------------------------------------------------


def test_registry_instruments_and_snapshot():
    r = reg.Registry()
    r.counter("steps").inc()
    r.counter("steps").inc(2)  # get-or-create returns the same instrument
    r.gauge("util").set(0.75)
    h = r.histogram("lat", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(99.0)
    snap = r.snapshot()
    assert snap["counters"]["steps"] == 3
    assert snap["gauges"]["util"] == 0.75
    assert snap["histograms"]["lat"]["count"] == 3
    assert snap["histograms"]["lat"]["buckets"] == [
        [0.1, 1], [1.0, 2], ["+Inf", 3]]  # cumulative
    assert snap["histograms"]["lat"]["sum"] == pytest.approx(99.55)
    json.dumps(snap)  # strict-JSON serializable (+Inf encoded as string)


def test_registry_counter_rejects_negative_and_type_conflicts():
    r = reg.Registry()
    with pytest.raises(ValueError):
        r.counter("c").inc(-1)
    r.counter("x")
    with pytest.raises(TypeError):
        r.gauge("x")


def test_prometheus_exposition_format():
    r = reg.Registry()
    r.counter("rows_total").inc(42)
    r.gauge("queue_depth").set(7)
    r.histogram("step_seconds", buckets=(0.5,)).observe(0.2)
    text = r.to_prometheus(labels={"node": "worker:0"})
    assert '# TYPE tfos_rows_total counter' in text
    assert 'tfos_rows_total{node="worker:0"} 42' in text
    assert 'tfos_queue_depth{node="worker:0"} 7' in text
    assert 'tfos_step_seconds_bucket{le="0.5",node="worker:0"} 1' in text
    assert 'tfos_step_seconds_bucket{le="+Inf",node="worker:0"} 1' in text
    assert 'tfos_step_seconds_sum{node="worker:0"} 0.2' in text
    assert 'tfos_step_seconds_count{node="worker:0"} 1' in text
    # every non-comment line is "name{labels} value"
    for line in text.strip().splitlines():
        if not line.startswith("#"):
            assert line.count(" ") == 1, line


def test_merge_snapshots_sums_counters_histograms_keeps_gauges_per_node():
    def one(n):
        r = reg.Registry()
        r.counter("rows").inc(n)
        r.gauge("depth").set(n)
        r.histogram("lat", buckets=(1.0,)).observe(n)
        return r.snapshot()

    merged = reg.merge_snapshots({"w0": one(1), "w1": one(10)})
    assert merged["counters"]["rows"] == 11
    assert merged["gauges"]["depth"] == {"w0": 1, "w1": 10}
    assert merged["histograms"]["lat"]["count"] == 2
    assert merged["histograms"]["lat"]["buckets"][-1] == ["+Inf", 2]
    text = reg.merged_to_prometheus(merged)
    assert "tfos_rows 11" in text
    assert 'tfos_depth{node="w0"} 1' in text


def test_metrics_reporter_carries_registry_and_aggregate_merges():
    """The kv-published step-metrics snapshot carries the registry section,
    and metrics.aggregate rolls registries up cluster-wide."""
    from tensorflowonspark_tpu import metrics

    class KV:
        def __init__(self):
            self.kv = {}

        def set(self, k, v):
            self.kv[k] = v

    r = reg.Registry()
    r.counter("trainer_steps_total").inc(5)
    kv = KV()
    rep = metrics.MetricsReporter(mgr=kv, interval=1, registry=r)
    rep(loss=1.0, examples=4, dt=0.1)
    snap = kv.kv["metrics"]
    assert snap["registry"]["counters"]["trainer_steps_total"] == 5

    agg = metrics.aggregate({"worker:0": snap,
                             "worker:1": dict(snap)})
    assert agg["registry"]["counters"]["trainer_steps_total"] == 10


def test_trainer_steps_feed_the_default_registry():
    """trainer.Trainer records step counters/histograms into the process
    registry (the series TFCluster.metrics() aggregates)."""
    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.trainer import Trainer

    before = obs.get_registry().snapshot()["counters"].get(
        "trainer_steps_total", 0)
    t = Trainer("mnist_mlp", config=mnist.Config.tiny())
    batch = mnist.example_batch(t.config, batch_size=8)
    t.step(batch)
    t.step(batch)
    after = obs.get_registry().snapshot()
    assert after["counters"]["trainer_steps_total"] == before + 2
    assert "trainer_step_seconds" in after["histograms"]

# ---------------------------------------------------------------------------
# trace identity + context propagation + tail-sampled request traces
# ---------------------------------------------------------------------------

import os  # noqa: E402
import sys  # noqa: E402

from tensorflowonspark_tpu.obs import trace as trace_lib  # noqa: E402


def test_spans_carry_linked_trace_identity():
    """Nested spans share one trace_id and link parent→child by span ID,
    not just by name; a sibling root starts a fresh trace; instant events
    inherit the enclosing span's identity."""
    tr = Tracer(node="t")
    with tr.span("outer"):
        with tr.span("inner"):
            tr.event("mark")
    with tr.span("other"):
        pass
    evs = {e["name"]: e for e in tr.snapshot()}
    outer, inner, mark = evs["outer"], evs["inner"], evs["mark"]
    assert len(outer["trace_id"]) == 32 and len(outer["span_id"]) == 16
    assert inner["trace_id"] == outer["trace_id"]
    assert inner["parent_span_id"] == outer["span_id"]
    assert "parent_span_id" not in outer
    assert mark["trace_id"] == outer["trace_id"]
    assert mark["parent_span_id"] == inner["span_id"]
    # a fresh root = a fresh trace
    assert evs["other"]["trace_id"] != outer["trace_id"]


def test_with_context_carries_trace_across_threads():
    """The explicit propagation API: a context minted on one thread makes
    spans on ANOTHER thread children of it — the hop the thread-local
    span stack cannot make."""
    tr = Tracer(node="t")
    handoff = {}

    def submitter():
        with tr.span("request") as sp:
            handoff["ctx"] = sp.context()

    submitter()
    ctx = handoff["ctx"]
    done = threading.Event()

    def worker():
        with tr.with_context(ctx):
            with tr.span("compute"):
                pass
        done.set()

    threading.Thread(target=worker).start()
    assert done.wait(5)
    evs = {e["name"]: e for e in tr.snapshot()}
    assert evs["compute"]["trace_id"] == ctx.trace_id
    assert evs["compute"]["parent_span_id"] == ctx.span_id
    # the ambient context is restored after the with-block
    assert tr.current_context() is None


def test_traceparent_parse_format_round_trip():
    ctx = trace_lib.TraceContext.new()
    parsed = trace_lib.parse_traceparent(trace_lib.format_traceparent(ctx))
    assert parsed == ctx
    good = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
    assert trace_lib.parse_traceparent(good).trace_id == "ab" * 16
    # lenient rejection: malformed headers are None, never an exception
    for bad in (None, "", "garbage", "00-short-cdcdcdcdcdcdcdcd-01",
                "ff-" + "ab" * 16 + "-" + "cd" * 8 + "-01",  # bad version
                "00-" + "0" * 32 + "-" + "cd" * 8 + "-01",  # zero trace
                "00-" + "ab" * 16 + "-" + "0" * 16 + "-01"):  # zero span
        assert trace_lib.parse_traceparent(bad) is None


def test_request_trace_builds_linked_tree_and_finish_races_once():
    rt = trace_lib.RequestTrace("online.request", tenant="a")
    rt.add("admission", 0.001, outcome="admitted")
    rt.add("queue", 0.002)
    rt.set(latency_ms=3.5)
    assert rt.finish(status="ok") is True
    assert rt.finish(status="timeout") is False  # loser of the race
    assert rt.add("late", 0.1) is None  # adds after finish are dropped
    doc = rt.to_doc()
    assert doc["status"] == "ok"
    assert doc["duration_ms"] > 0
    names = [s["name"] for s in doc["spans"]]
    assert names == ["admission", "queue", "online.request"]
    root = doc["spans"][-1]
    assert root["span_id"] == doc["root_span_id"]
    assert root["attrs"]["latency_ms"] == 3.5
    for child in doc["spans"][:-1]:
        assert child["parent_span_id"] == doc["root_span_id"]
        assert child["trace_id"] == doc["trace_id"]


def test_request_trace_joins_inbound_context():
    up = trace_lib.TraceContext.new()
    rt = trace_lib.RequestTrace("online.request", ctx=up)
    rt.finish()
    doc = rt.to_doc()
    assert doc["trace_id"] == up.trace_id
    assert doc["parent_span_id"] == up.span_id
    assert doc["root_span_id"] != up.span_id


def test_trace_store_tail_retention_and_bound(monkeypatch):
    """Retention: tail reasons always keep; no reason rolls the uniform
    sample (0 → dropped whole, 1 → kept); the ring stays bounded."""
    store = trace_lib.TraceStore(capacity=3)

    def commit(retain=None, sample=None):
        rt = trace_lib.RequestTrace("online.request")
        rt.finish(status="ok")
        return store.commit(rt, retain=retain, sample=sample)

    assert commit(retain="slo_breach") == "slo_breach"
    assert commit(sample=0.0) is None  # dropped at commit, no residue
    assert commit(sample=1.0) == "sampled"
    assert store.committed == 3 and store.retained_total == 2
    for _ in range(5):
        commit(retain="error")
    assert len(store.recent(limit=100)) == 3  # ring bound holds
    doc = store.to_doc()
    assert doc["committed"] == 8
    assert doc["dropped_total"] == 1
    # slowest-first ordering contract
    durs = [t["duration_ms"] for t in doc["retained"]]
    assert durs == sorted(durs, reverse=True)
    # env knob drives the default sample
    monkeypatch.setenv("TFOS_TRACE_SAMPLE", "0")
    assert commit() is None
    monkeypatch.setenv("TFOS_TRACE_SAMPLE", "1")
    assert commit() == "sampled"
    monkeypatch.setenv("TFOS_TRACE_REQUESTS", "0")
    assert trace_lib.requests_enabled() is False


def test_trace_store_events_merge_into_chrome_trace(tmp_path):
    """Retained request spans merge into the Chrome timeline with their
    trace identity in args (searchable in the viewer), and the result
    passes the schema gate."""
    store = trace_lib.TraceStore(capacity=4)
    rt = trace_lib.RequestTrace("online.request", node="t", tenant="a")
    rt.add("forward", 0.002, batch_id=7)
    rt.finish()
    store.commit(rt, retain="slo_breach")
    doc = chrome.merge({"t": store.events()})
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert spans
    for ev in spans:
        assert ev["args"]["trace_id"] == rt.ctx.trace_id
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import check_trace

    assert check_trace.validate_doc(doc) == []


# ---------------------------------------------------------------------------
# labeled series + exemplars + OpenMetrics exposition
# ---------------------------------------------------------------------------


def test_labeled_series_share_one_family_type_line():
    r = reg.Registry()
    r.counter("req_total").inc(3)
    r.counter("req_total", labels={"tenant": "a"}).inc()
    r.counter("req_total", labels={"tenant": "b"}).inc(2)
    text = reg.snapshot_to_prometheus(r.snapshot())
    assert text.count("# TYPE tfos_req_total counter") == 1
    assert 'tfos_req_total 3' in text
    assert 'tfos_req_total{tenant="a"} 1' in text
    assert 'tfos_req_total{tenant="b"} 2' in text
    from tensorflowonspark_tpu.obs import httpd
    assert httpd.validate_prometheus_text(text) == []


def test_labeled_cardinality_bounded_with_overflow_and_remove(monkeypatch):
    monkeypatch.setattr(reg, "_DEFAULT_SERIES_MAX", 2)
    r = reg.Registry()
    a = r.counter("x_total", labels={"tenant": "a"})
    b = r.counter("x_total", labels={"tenant": "b"})
    # over the bound: collapses into the _overflow series, not unbounded
    c = r.counter("x_total", labels={"tenant": "c"})
    d = r.counter("x_total", labels={"tenant": "d"})
    assert c is d
    assert c.name == 'x_total{tenant="_overflow"}'
    assert a is r.counter("x_total", labels={"tenant": "a"})  # idempotent
    # eviction with the owner frees the slot for a new label set
    assert r.remove("x_total", {"tenant": "a"}) is True
    assert r.remove("x_total", {"tenant": "a"}) is False
    e = r.counter("x_total", labels={"tenant": "e"})
    assert e.name == 'x_total{tenant="e"}'
    # removing the UNCOUNTED _overflow series must not erode the bound:
    # repeated overflow create/remove cycles would otherwise let the
    # family grow past its cap one slot at a time
    assert r.remove("x_total", {"tenant": "_overflow"}) is True
    f = r.counter("x_total", labels={"tenant": "f"})
    assert f.name == 'x_total{tenant="_overflow"}'  # still over the bound
    del b


def test_histogram_exemplar_exposition_and_byte_identical_without():
    """Classic exposition never changes (exemplars or not); the
    OpenMetrics flavor annotates the owning bucket line and terminates
    with # EOF; both validators accept their own format."""
    from tensorflowonspark_tpu.obs import httpd

    def build(with_exemplar):
        r = reg.Registry()
        h = r.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0))
        h.observe(0.05, exemplar={"trace_id": "ab" * 16}
                  if with_exemplar else None)
        return r

    plain = build(False)
    traced = build(True)
    assert (reg.snapshot_to_prometheus(plain.snapshot())
            == reg.snapshot_to_prometheus(traced.snapshot()))
    om = traced.to_openmetrics()
    want = ('tfos_lat_seconds_bucket{le="0.1"} 1 '
            '# {trace_id="' + "ab" * 16 + '"}')
    assert want in om
    assert om.rstrip().endswith("# EOF")
    assert httpd.validate_openmetrics_text(om) == []
    assert httpd.validate_prometheus_text(om.replace("# EOF\n", "")) == []
    # classic mode without EOF is fine; openmetrics without EOF is not
    assert httpd.validate_openmetrics_text(
        reg.snapshot_to_prometheus(traced.snapshot())) != []


def test_exemplars_survive_snapshot_merge_freshest_wins():
    r1, r2 = reg.Registry(), reg.Registry()
    h1 = r1.histogram("lat_seconds", buckets=(0.1,))
    h2 = r2.histogram("lat_seconds", buckets=(0.1,))
    h1.observe(0.05, exemplar={"trace_id": "aa" * 16})
    time.sleep(0.01)
    h2.observe(0.06, exemplar={"trace_id": "bb" * 16})
    merged = reg.merge_snapshots({"n1": r1.snapshot(), "n2": r2.snapshot()})
    ex = merged["histograms"]["lat_seconds"]["exemplars"]["0.1"]
    assert ex[0]["trace_id"] == "bb" * 16  # freshest ts won
    # an exemplar-free merge keeps the historical export shape
    r3 = reg.Registry()
    r3.histogram("lat_seconds", buckets=(0.1,)).observe(0.01)
    merged = reg.merge_snapshots({"n": r3.snapshot()})
    assert "exemplars" not in merged["histograms"]["lat_seconds"]


def test_openmetrics_validator_catches_violations():
    from tensorflowonspark_tpu.obs import httpd

    bad_exemplar = ('# TYPE m histogram\n'
                    'm_bucket{le="+Inf"} 1 # not-an-exemplar 1\n'
                    'm_sum 1\nm_count 1\n# EOF\n')
    assert any("exemplar" in p
               for p in httpd.validate_openmetrics_text(bad_exemplar))
    on_non_bucket = ('# TYPE m counter\n'
                     'm 1 # {trace_id="ab"} 1\n# EOF\n')
    assert any("non-bucket" in p
               for p in httpd.validate_openmetrics_text(on_non_bucket))
    after_eof = '# TYPE m counter\nm 1\n# EOF\nm 2\n'
    assert any("after" in p
               for p in httpd.validate_openmetrics_text(after_eof))


def test_series_label_values_with_backslashes_round_trip():
    """split_series/_unescape must decode escaped values in one pass —
    'C:\\new' must NOT come back with a newline in it."""
    key = reg.series_key("m_total", {"path": "C:\\new", "q": 'say "hi"\n'})
    fam, labels = reg.split_series(key)
    assert fam == "m_total"
    assert labels == {"path": "C:\\new", "q": 'say "hi"\n'}


def test_validator_does_not_missplit_hash_inside_label_value():
    from tensorflowonspark_tpu.obs import httpd

    text = ('# TYPE m counter\n'
            'm{path="/a # b"} 1\n'
            'm{path="/a # {x"} 2\n')
    assert httpd.validate_prometheus_text(text) == []


# ---------------------------------------------------------------------------
# hostile-input hardening (ISSUE 16): traceparent, label names, exemplars
# ---------------------------------------------------------------------------


def test_traceparent_hostile_and_future_version_inputs():
    """Malformed or hostile headers reject cheaply; future W3C versions
    parse their first four fields (the spec's forward-compat rule)."""
    good_tail = "ab" * 16 + "-" + "cd" * 8 + "-01"
    # future version: extra dash-separated members are ignored
    fut = trace_lib.parse_traceparent("01-" + good_tail + "-extra-stuff")
    assert fut is not None and fut.trace_id == "ab" * 16
    # version 00 is exactly four fields: trailing members reject
    assert trace_lib.parse_traceparent("00-" + good_tail + "-extra") is None
    # version ff is forbidden by the spec even with extra members
    assert trace_lib.parse_traceparent("ff-" + good_tail + "-x") is None
    # oversized header: bounded rejection, no regex work on megabytes
    assert trace_lib.parse_traceparent(
        "01-" + good_tail + "-" + "a" * 600) is None
    assert trace_lib.parse_traceparent("00-" + "a" * 4096) is None


def test_label_names_sanitized_in_series_key():
    """A label NAME with exposition-breaking runes must never reach the
    text format: invalid runes map to '_', a leading digit is prefixed,
    and colliding raw names resolve deterministically (last raw key
    wins) instead of emitting a duplicate label."""
    from tensorflowonspark_tpu.obs import httpd

    key = reg.series_key("m_total", {"bad name": "v1", "0lead": "v2"})
    fam, labels = reg.split_series(key)
    assert fam == "m_total"
    assert labels == {"bad_name": "v1", "_0lead": "v2"}
    # collision: both sanitize to 'a_b'; one survives, deterministically
    key = reg.series_key("m_total", {"a b": "first", "a:b": "second"})
    _, labels = reg.split_series(key)
    assert labels == {"a_b": "second"}
    # the sanitized series must render into a VALID exposition
    r = reg.Registry()
    r.counter("m_total", labels={"bad name": "v"}).inc()
    text = reg.snapshot_to_prometheus(r.snapshot())
    assert 'bad_name="v"' in text
    assert httpd.validate_prometheus_text(text) == []


def test_exemplar_label_budget_keeps_trace_id():
    """OpenMetrics caps exemplar label runes at 128: oversized exemplar
    labels are truncated/dropped but the trace_id — the whole point of
    the exemplar — always survives intact."""
    import re

    from tensorflowonspark_tpu.obs import httpd

    r = reg.Registry()
    h = r.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0))
    h.observe(0.05, exemplar={"trace_id": "ab" * 16,
                              "note": "x" * 500, "z" * 60: "y" * 60})
    om = r.to_openmetrics()
    assert 'trace_id="' + "ab" * 16 + '"' in om
    assert httpd.validate_openmetrics_text(om) == []
    # the emitted exemplar obeys the 128-rune budget
    for line in om.splitlines():
        if " # {" in line:
            labels = re.findall(r'([a-zA-Z0-9_]+)="([^"]*)"',
                                line.split(" # {", 1)[1])
            assert sum(len(k) + len(v) for k, v in labels) <= 128
            assert dict(labels)["trace_id"] == "ab" * 16
            break
    else:
        raise AssertionError("no exemplar line emitted")


def test_exposition_validator_catches_malformed_labels_and_fat_exemplars():
    """The quote-aware validator: a label block that is not name="value"
    pairs is flagged, and an exemplar past the 128-rune budget is
    flagged with the bound in the message."""
    from tensorflowonspark_tpu.obs import httpd

    bad_block = ('# TYPE m counter\n'
                 'm{tenant=unquoted} 1\n')
    assert any("label block" in p
               for p in httpd.validate_prometheus_text(bad_block))
    fat = ('# TYPE m histogram\n'
           'm_bucket{le="+Inf"} 1 # {trace_id="' + "ab" * 16 + '",'
           'note="' + "x" * 200 + '"} 0.05\n'
           '# EOF\n')
    assert any("128" in p
               for p in httpd.validate_openmetrics_text(fat))
    # a value containing '}' or spaces inside quotes must NOT trip it
    ok = ('# TYPE m counter\n'
          'm{q="a } b, c=d"} 1\n')
    assert httpd.validate_prometheus_text(ok) == []
