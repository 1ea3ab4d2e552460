"""``program_spans.py`` and the per-layer readers built on it: on hand-made
records, on the fixture cells end to end on the CPU (the job's
``obs/trace.json`` is there, every new reader finds its spans, a turn-round
is counted once a partition end, no program span carries a name
``trace_reduce`` reads), and on a recorded v5e trace with program spans."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import program_spans as ps, spec, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
OVERLAY = os.path.join(HERE, "fixtures", "overlay")
RECORDED = os.path.join(HERE, "fixtures", "tiny_spans_v5e.xplane.pb.gz")
#: the metrics PR 24 added, and which fixture cell each is read on
NEW = {
    "health_probe_s": (), "reader_parse_ms": ("tiny_fed_4chip",),
    "reader_stack_ms": ("tiny_fed_4chip",),
    "feed_stage_ms": ("tiny_fed_4chip", "tiny_spark"),
    "feed_turnround_s": ("tiny_spark",), "feed_turnround_pct": ("tiny_spark",),
    "feeder_task_gap_s": ("tiny_spark",),
    "feeder_first_row_s": ("tiny_spark",),
    "feeder_drain_wait_s": ("tiny_spark",),
    "step_enqueue_ms": ("tiny_fed_4chip", "tiny_spark"),
    "idle_staging_pct": (), "device_forward_ms": (),
    "device_backward_ms": (), "device_optimizer_ms": (),
    "cache_disk_writes": ("tiny_fed_4chip", "tiny_spark"),
}


def test_benchmark_new_modules_import_no_jax():
    """The launcher's no-JAX rule, for the modules PR 24 added."""
    modules = ", ".join(["benchmark.program_spans"]
                        + [f"benchmark.metrics.{m}" for m in NEW])
    code = ("import sys; sys.path.insert(0, %r); import %s; "
            "bad = [m for m in sys.modules if m == 'jax' or "
            "m.startswith(('jax.', 'jaxlib'))]; assert not bad, bad"
            % (REPO, modules))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_benchmark_every_new_metric_is_declared_with_a_reader():
    spec_ = spec.load(REPO)
    declared = {m["name"]: m for m in spec_["per_layer"]}
    for name in NEW:
        assert "workloads" in declared[name], name
        assert os.path.isfile(os.path.join(REPO, "benchmark", "metrics",
                                           name + ".py"))


# -- hand-made records --------------------------------------------------------


def _span(name, t0, dur, span_id=None, parent=None, pid=2, **args):
    if span_id:
        args["span_id"] = span_id
    if parent:
        args["parent_span_id"] = parent
    return {"name": name, "ph": "X", "ts": t0 * 1e6, "dur": dur * 1e6,
            "pid": pid, "tid": 1, "args": args}


def _run(tmp_path, monkeypatch, events, dropped=0, counters=None):
    monkeypatch.setattr(ps, "ROOT", str(tmp_path))
    obs_dir = tmp_path / ".benchmark_out" / "cell" / "scratch" / "app" / "obs"
    obs_dir.mkdir(parents=True)
    (obs_dir / "trace.json").write_text(json.dumps(
        {"traceEvents": events, "tfos": {"dropped": {"worker:0": dropped}}}))
    if counters is not None:
        (obs_dir / "counters.json").write_text(json.dumps(counters))
    return {"cell": {"name": "cell"}, "notes": [], "t_launch": 90.0,
            "driver": {"t_cluster_run": 95.0},
            "trainer": {"t_map_fun": 99.0, "t_window_start": 100.0,
                        "window": {"seconds": 10.0}}}


def test_benchmark_spans_are_cut_to_the_window(tmp_path, monkeypatch):
    events = [_span("feed.stage", 99.5, 1.0),       # straddles the start
              _span("feed.stage", 101.0, 0.010),
              _span("feed.stage", 102.0, 0.030),
              _span("feed.stage", 103.0, 0.020),
              _span("feed.stage", 109.99, 0.5),     # straddles the end
              _span("health.probe", 96.0, 2.5)]
    run = _run(tmp_path, monkeypatch, events)
    assert [round(s["t1"] - s["t0"], 3) for s in ps.spans(run, "feed.stage")
            ] == [0.010, 0.030, 0.020]
    assert ps.median_ms(run, "feed.stage") == pytest.approx(20.0)
    assert ps.spans(run, "reader.parse") is None    # the program has none
    assert ps.median_ms(run, "reader.parse") is None
    assert len(ps.spans(run, "feed.stage", whole_job=True)) == 5
    from benchmark.metrics import health_probe_s

    assert health_probe_s.read(run) == pytest.approx(2.5)
    with open(tmp_path / ".benchmark_out" / "cell" /
              "program_spans.json") as f:
        summary = json.load(f)
    assert summary["spans"]["feed.stage"]["count"] == 3
    assert summary["bootstrap"]["seconds"] == pytest.approx(4.0)
    assert summary["bootstrap"]["by_span"] == {
        "health.probe": pytest.approx(2.5)}
    assert summary["bootstrap"]["uncovered"] == [
        [pytest.approx(0.0), pytest.approx(1.0)],
        [pytest.approx(3.5), pytest.approx(4.0)]]


def test_benchmark_dropped_events_make_every_reader_return_none(
        tmp_path, monkeypatch):
    from benchmark.metrics import (feed_stage_ms, feed_turnround_pct,
                                   step_enqueue_ms)

    events = [_span("feed.stage", 101.0, 0.010),
              _span("trainer.dispatch", 101.0, 0.001),
              _span("feed.turnround", 104.0, 1.0)]
    whole = _run(tmp_path / "a", monkeypatch, events)
    assert feed_stage_ms.read(whole) == pytest.approx(10.0)
    assert step_enqueue_ms.read(whole) == pytest.approx(1.0)
    assert feed_turnround_pct.read(whole) == pytest.approx(10.0)
    partial = _run(tmp_path / "b", monkeypatch, events, dropped=3)
    for reader in (feed_stage_ms, step_enqueue_ms, feed_turnround_pct):
        assert reader.read(partial) is None
    assert any("3 events were dropped" in n for n in partial["notes"])


def test_benchmark_a_program_with_no_record_reads_as_nothing(
        tmp_path, monkeypatch):
    """The parent of the PR that added the spans: no file, no metric."""
    monkeypatch.setattr(ps, "ROOT", str(tmp_path))
    run = {"cell": {"name": "cell"}, "notes": [], "trainer": {
        "t_window_start": 100.0, "window": {"seconds": 10.0}}}
    for name in NEW:
        reader = spec.module("benchmark", "metrics", name)
        assert reader.read(run) is None, name
    assert run["notes"] == []


def test_benchmark_turnround_share_gaps_and_self_time(tmp_path, monkeypatch):
    from benchmark.metrics import (cache_disk_writes, feed_turnround_pct,
                                   feed_turnround_s, feeder_task_gap_s)

    events = [
        _span("feed.turnround", 99.0, 2.0),             # half inside
        _span("feed.turnround", 104.0, 1.5),
        _span("feed.turnround", 109.5, 2.0),            # a quarter inside
        _span("feeder.task", 100.5, 2.0, pid=3),
        _span("feeder.task", 103.0, 2.0, pid=3),
        _span("feeder.task", 105.75, 2.0, pid=3),
        _span("reader.batch", 101.0, 0.100, span_id="b1"),
        _span("reader.parse", 101.0, 0.060, parent="b1"),
        _span("feed.stage", 101.070, 0.020, parent="b1"),
    ]
    run = _run(tmp_path, monkeypatch, events, counters={
        "worker:0:11": {"counters": {"compile_cache_disk_writes_total": 2}},
        "worker:0:12": {"counters": {"other_total": 5}}})
    assert feed_turnround_pct.read(run) == pytest.approx(
        100.0 * (1.0 + 1.5 + 0.5) / 10.0)
    assert feed_turnround_s.read(run) == pytest.approx(1.5)  # one inside
    assert "1 turn-rounds inside the window" in run["notes"][-1]
    assert feeder_task_gap_s.read(run) == pytest.approx(0.625)
    assert cache_disk_writes.read(run) == 2
    assert ps.self_seconds(ps.load(run), "reader.batch") == pytest.approx(
        0.100 - 0.060 - 0.020)


def test_benchmark_clock_offset_places_another_process(tmp_path, monkeypatch):
    """The offset from the paired ``trainer.step`` spans puts a feeder's ring
    span (another process, the same wall clock) on the profiler's clock to
    within the pairs' spread."""
    true_offset = -1234.5
    jitter = [0.0004, -0.0003, 0.0001, 0.0008, -0.0006, 0.0002]
    events = [_span("trainer.step", 101.0 + i, 0.05, step=7 + i)
              for i in range(len(jitter))]
    events.append(_span("feeder.drain_wait", 103.25, 0.5, pid=5))
    run = _run(tmp_path, monkeypatch, events)
    reduced = {"step_starts": {str(7 + i): 101.0 + i + true_offset + j
                               for i, j in enumerate(jitter)}}
    clock = ps.clock(run, reduced)
    assert clock["pairs"] == 6
    assert clock["offset_s"] == pytest.approx(true_offset, abs=1e-3)
    assert 0 < clock["spread_s"] < 2e-3
    run["_traced"] = {"clock": clock}
    ((t0, t1),) = ps.on_profiler_clock(run, "feeder.drain_wait")
    assert abs(t0 - (103.25 + true_offset)) <= clock["spread_s"]
    assert t1 - t0 == pytest.approx(0.5)


def test_benchmark_phase_of_an_op_name():
    assert ps.phase_of("jit(_step)/jit(main)/jvp(forward)/Dense_0/dot_general"
                       ) == "forward"
    assert ps.phase_of("jit(_step)/jit(main)/transpose(jvp(forward))/Dense_0/"
                       "dot_general") == "backward"
    assert ps.phase_of("jit(_step)/jit(main)/optimizer/mul") == "optimizer"
    assert ps.phase_of("jit(_step)/jit(main)/forward/reduce_sum") == "forward"
    assert ps.phase_of("jit(_step)/mul:") == "optimizer"
    assert ps.phase_of("jit(_step)/transpose(jvp(ResNet))/conv") == "backward"
    assert ps.phase_of("jit(_step)/jvp(ResNet)/Dense_0/dot") == "forward"


def test_benchmark_op_names_are_read_from_the_wire_format():
    """PR 23's recorded trace has no phase scopes but does carry each
    operation's ``op_name`` in its event metadata."""
    import gzip

    fixture = os.path.join(HERE, "fixtures", "tiny_resnet_v5e.xplane.pb.gz")
    with gzip.open(fixture, "rb") as f:
        names = ps.op_names(f.read())
    (plane,) = names
    assert plane == "/device:TPU:0"
    ops = names[plane]
    assert len(ops) > 300
    assert any(v.startswith("jit(_step)/transpose(jvp(ResNet))/")
               for v in ops.values())
    reduced = ps.reduce_xplane(fixture)
    assert reduced["steps"] == 4 and reduced["step_starts"] == {}
    assert reduced["host_spans"] == {}      # no program span in PR 23's
    assert set(reduced["phase_s"]) == {"forward", "backward", "optimizer",
                                       "unnamed"}
    assert reduced["ops_with_op_name"] > 1000
    whole = trace_reduce.reduce_file(fixture)
    assert reduced["window"][1] - reduced["window"][0] == pytest.approx(
        whole["window_s"])
    idle = sum(trace_reduce.total(g) for g in reduced["idle_gaps"])
    assert idle == pytest.approx(whole["window_s"] - whole["busy_s"])


# -- the fixture cells, end to end on the CPU ---------------------------------


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of ``benchmark/`` with the fixture overlay, whose
    ``BENCHMARK.json`` gets the new metrics' entries from the repository's,
    each listing the fixture cells it is read on."""
    root = tmp_path_factory.mktemp("spans_tree")
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(OVERLAY, root, dirs_exist_ok=True)
    with open(root / "BENCHMARK.json") as f:
        fixture = json.load(f)
    real = {m["name"]: m for m in spec.load(REPO)["per_layer"]}
    for name, cells in NEW.items():
        fixture["per_layer"].append(dict(
            real[name], workloads=real[name]["workloads"] + list(cells)))
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(fixture, f)
    return root


def _run_cell(tree, workload, devices):
    # the pickled transport: tests elsewhere in the suite list /dev/shm, and
    # test_benchmark_e2e.py already runs this cell over shared memory
    env = dict(os.environ, JAX_PLATFORMS="cpu", TFOS_COMPILE_CACHE="0",
               TFOS_HOST_DEVICE_COUNT=str(devices), PYTHONPATH=REPO,
               TFOS_FEED_SHM="0")
    for inherited in ("TFOS_NUM_CHIPS", "XLA_FLAGS"):   # conftest's own
        env.pop(inherited, None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "fixtures", "run_cell.py"),
         str(tree), workload, str(2 ** 31 + 2424), "2", "1"],
        capture_output=True, text=True, timeout=600, env=env, cwd=str(tree))
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    lines = proc.stdout.strip().splitlines()
    out = tree / ".benchmark_out" / workload
    (trace_path,) = out.glob("scratch/*/obs/trace.json")
    with open(trace_path) as f:
        trace = json.load(f)
    with open(out / "trainer_report.json") as f:
        report = json.load(f)
    return json.loads(lines[-1]), lines[:-1], trace, report


def _check_names(trace):
    """No program span under a name the benchmark's own reduction reads."""
    names = {ev["name"] for ev in trace["traceEvents"] if ev["ph"] == "X"}
    taken = set(trace_reduce.SPANS) | {trace_reduce.WINDOW_SPAN}
    assert not names & taken, names & taken
    assert not [n for n in names if trace_reduce.TRANSFER.search(n)]
    assert all("." in n for n in names), names
    return names


def test_benchmark_spark_fixture_cell_writes_the_job_trace(tree):
    result, lines, trace, report = _run_cell(tree, "tiny_spark", devices=1)
    assert result["correct"] is True, lines
    for name, cells in NEW.items():
        assert (name in result["metrics"]) == ("tiny_spark" in cells), name
    assert result["metrics"]["cache_disk_writes"]["value"] == 0
    assert 0 < result["metrics"]["feed_turnround_pct"]["value"] < 100
    names = _check_names(trace)
    assert {"cluster.reserve", "cluster.feed_epoch", "spark.task_send",
            "executor.task", "executor.task_load", "node.manager_start",
            "node.register_await", "node.trainer_spawn", "node.chip_verify",
            "node.map_fun", "trainer.init", "feeder.task", "feeder.connect",
            "feeder.first_row", "feeder.send", "feeder.drain_wait",
            "feed.queue_wait", "feed.ingest", "feed.collate", "feed.stage",
            "feed.pump_blocked", "feed.wait", "feed.turnround",
            "trainer.step", "trainer.shard", "trainer.dispatch",
            "cluster.shutdown"} <= names
    assert sum(trace["tfos"]["dropped"].values()) == 0
    spans = [ev for ev in trace["traceEvents"] if ev["ph"] == "X"]

    def named(name):
        return sorted((ev for ev in spans if ev["name"] == name),
                      key=lambda ev: ev["ts"])

    # a turn-round is counted once a partition end: each runs from the end
    # of one feeder's send to inside the next feeder task, and there is one
    # fewer than partitions sent to a live trainer (the last has no next)
    sends = [ev for ev in named("feeder.send") if ev["args"].get("rows")]
    turns = named("feed.turnround")
    assert len(sends) >= 4
    assert len(sends) - 1 <= len(turns) + 1 and len(turns) <= len(sends)
    for turn, nxt in zip(turns, sends[1:]):
        assert turn["ts"] + turn["dur"] >= nxt["ts"] - 1e3
    # the feeder's send carries the sums of its chunks' timings
    assert all(ev["args"]["chunks"] >= 1 and ev["args"]["encode_s"] > 0
               for ev in sends)
    # inside and outside views agree: one trainer.step a window step
    lo = report["t_window_start"] * 1e6
    hi = report["t_window_end"] * 1e6
    inside = [ev for ev in named("trainer.step")
              if ev["ts"] >= lo and ev["ts"] + ev["dur"] <= hi]
    assert abs(len(inside) - report["window"]["steps"]) <= 2
    steps = [ev["args"]["step"] for ev in named("trainer.step")]
    assert steps == list(range(1, len(steps) + 1))
    ids = {ev["args"]["trace_id"] for ev in named("trainer.step")}
    assert len(ids) == len(steps)       # a trace id of its own a step


def test_benchmark_readers_fixture_cell_writes_the_job_trace(tree):
    result, lines, trace, report = _run_cell(tree, "tiny_fed_4chip",
                                             devices=4)
    assert result["correct"] is True, lines
    for name, cells in NEW.items():
        assert (name in result["metrics"]) == ("tiny_fed_4chip" in cells), name
    names = _check_names(trace)
    assert {"reader.batch", "reader.parse", "reader.stack", "feed.stage",
            "feed.pump_blocked", "feed.wait", "readers.epoch", "trainer.step",
            "trainer.dispatch", "node.map_fun", "node.chip_verify"} <= names
    spans = [ev for ev in trace["traceEvents"] if ev["ph"] == "X"]
    batches = [ev for ev in spans if ev["name"] == "reader.batch"]
    by_parent = {}
    for ev in spans:
        by_parent.setdefault(ev["args"].get("parent_span_id"), []).append(
            ev["name"])
    for batch in batches:       # each batch has exactly its three children
        assert sorted(by_parent[batch["args"]["span_id"]]) == [
            "feed.stage", "reader.parse", "reader.stack"], batch
        assert batch["args"]["records"] > 0 and batch["args"]["bytes"] > 0
    # the benchmark's own spans are in the profile, the program's beside
    # them on the same clock: the CPU rehearsal has no device plane, so the
    # child is run on the file directly
    xplane = sorted((tree / ".benchmark_out" / "tiny_fed_4chip").glob(
        "trace/**/*.xplane.pb"))[-1]
    with pytest.raises(ValueError):
        ps.reduce_xplane(str(xplane))   # no device plane on the CPU


# -- a recorded v5e trace with program spans ----------------------------------


def test_benchmark_recorded_v5e_trace_pins_idle_staging_and_phases():
    """Six steps of the tiny ResNet preset on one v5e chip, fed by the
    program's pump and staged under its ``feed.stage`` span
    (``fixtures/record_spans_trace.py``, PR 24).  The expected figures come
    from the profiler's own Perfetto export of the same session, reduced by
    that script's ``--expected`` with arithmetic of its own (the export
    rounds to its own grid: the two readings agree to a percent)."""
    reduced = ps.reduce_xplane(RECORDED)
    with open(RECORDED.replace(".xplane.pb.gz", ".expected.json")) as f:
        expected = json.load(f)
    assert reduced["steps"] == expected["steps"] == 6
    lo, hi = reduced["window"]
    assert hi - lo == pytest.approx(expected["window_s"], rel=1e-6)
    idle = sum(trace_reduce.total(g) for g in reduced["idle_gaps"])
    assert idle == pytest.approx(expected["idle_s"], rel=1e-3)
    staged = reduced["host_spans"]["feed.stage"]
    assert len(staged) == expected["feed_stage_spans"] == 4
    assert 100 * ps.idle_under(reduced, staged) / (hi - lo) == pytest.approx(
        expected["idle_staging_pct"], rel=1e-3)
    assert sorted(reduced["step_starts"]) == expected["step_numbers"]
    for phase in ("forward", "backward", "optimizer", "unnamed"):
        assert 1e3 * reduced["phase_s"][phase] / reduced["steps"] == \
            pytest.approx(expected[f"device_{phase}_ms"], rel=2e-2)
    # every operation event is in exactly one phase
    whole = trace_reduce.reduce_file(RECORDED)
    assert sum(reduced["phase_s"].values()) >= whole["busy_s"]
    assert reduced["ops_with_op_name"] == 1878 and reduced["ops"] == 4056


def test_benchmark_recorded_ring_and_trace_give_the_clock_offset(
        tmp_path, monkeypatch):
    """The same run's ring: its ``trainer.step`` spans pair with the
    trace's by ``step``; the offset they give places the pump thread's
    ring ``feed.stage`` spans on the trace's own ``feed.stage`` events."""
    with open(RECORDED.replace(".xplane.pb.gz", ".ring.json")) as f:
        ring = json.load(f)
    assert ring["device_kind"] == "TPU v5 lite"
    events = [{"name": e["name"], "ph": "X", "ts": e["ts"], "dur": e["dur"],
               "pid": 1, "tid": e["tid"], "args": e.get("attrs") or {}}
              for e in ring["events"]]
    run = _run(tmp_path, monkeypatch, events)
    reduced = ps.reduce_xplane(RECORDED)
    clock = ps.clock(run, json.loads(json.dumps(reduced)))
    assert clock["pairs"] == 6
    assert clock["spread_s"] < 5e-6     # one host clock under both records
    run["_traced"] = {"clock": clock}
    placed = ps.on_profiler_clock(run, "feed.stage")
    lo, hi = reduced["window"]
    inside = [iv for iv in placed if iv[0] >= lo and iv[1] <= hi]
    traced = [iv for iv in reduced["host_spans"]["feed.stage"]
              if iv[0] > lo and iv[1] < hi]
    assert len(inside) == len(traced) >= 3
    for (a0, a1), (b0, b1) in zip(inside, traced):
        assert abs(a0 - b0) < 20e-6 and abs(a1 - b1) < 20e-6
