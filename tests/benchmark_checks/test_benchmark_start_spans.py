"""``benchmark/start_spans.py`` and the eight per-layer readers built on it
(PR 53): on a hand-made ring, and on a fixture cell end to end on the CPU,
where ``compile_cache.py``'s listener writes the ``jit.*`` spans."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import program_spans as ps, spec, start_spans

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
OVERLAY = os.path.join(HERE, "fixtures", "overlay")
#: name -> (unit, source, layer), in BENCHMARK.json's order
NEW = {
    "first_step_trace_s": ("s", "program_span", "trainer"),
    "first_step_lower_s": ("s", "program_span", "trainer"),
    "first_step_load_s": ("s", "program_span", "compile cache"),
    "first_step_run_s": ("s", "program_span", "trainer"),
    "init_jit_s": ("s", "program_span", "trainer"),
    "setup_jit_s": ("s", "program_span", "trainer"),
    "jit_traces": ("count", "program_counter", "trainer"),
    "cache_disk_misses": ("count", "program_counter", "compile cache"),
}
FIXTURE_CELLS = ("tiny_fed_4chip", "tiny_spark")
TRAINER, THREAD = 2, 1


def _read(name, run):
    return spec.module("benchmark", "metrics", name).read(run)


def test_benchmark_start_span_modules_import_no_jax():
    modules = ", ".join(["benchmark.start_spans"]
                        + [f"benchmark.metrics.{m}" for m in NEW])
    code = ("import sys; sys.path.insert(0, %r); import %s; "
            "bad = [m for m in sys.modules if m == 'jax' or "
            "m.startswith(('jax.', 'jaxlib'))]; assert not bad, bad"
            % (REPO, modules))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


@pytest.mark.parametrize("name", NEW)
def test_benchmark_start_span_metric_is_declared_in_every_cell(name):
    spec_ = spec.load(REPO)
    (entry,) = [m for m in spec_["per_layer"] if m["name"] == name]
    unit, source, layer = NEW[name]
    assert (entry["unit"], entry["source"], entry["layer"]) == (
        unit, source, layer)
    assert entry["moves"] == "setup_s" and entry["better"] == "lower"
    assert entry["workloads"] == [w["name"] for w in spec_["workloads"]]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    reader = spec.module("benchmark", "metrics", name)
    assert callable(reader.read) and reader.__doc__


def test_benchmark_start_span_metrics_end_the_per_layer_list():
    names = [m["name"] for m in spec.load(REPO)["per_layer"]]
    first = names.index("first_step_trace_s")
    assert names[first:first + len(NEW)] == list(NEW)


# -- a hand-made ring ---------------------------------------------------------


def _span(name, t0, t1, pid=TRAINER, tid=THREAD, **args):
    return {"name": name, "ph": "X", "ts": t0 * 1e6, "dur": (t1 - t0) * 1e6,
            "pid": pid, "tid": tid, "args": args}


def _jit(phase, fun, t0, t1, **kw):
    return _span("jit." + phase, t0, t1, fun=fun, **kw)


def _events():
    """A warm start: launched at 90, the ``map_fun`` from 95, ``Trainer()``
    over [100, 110], step 1 over [112, 119.9] and done by 120, the output
    check's compile at 121, the window from 130."""
    return [
        _span("node.map_fun", 94.0, 200.0),
        _span("trainer.init", 100.0, 110.0),
        _jit("trace", "_init", 100.5, 101.5),
        _jit("trace", "_uniform", 100.7, 101.0),       # nested: once
        _jit("lower", "jit(_init)", 101.5, 102.0),
        _jit("compile", "jit(_init)", 102.0, 104.0, cache="hit",
             retrieval_s=1.5, saved_s=30.0),
        _span("trainer.step", 112.0, 119.9, step=1),
        _span("trainer.shard", 112.0, 112.1, parent="trainer.step"),
        _span("trainer.dispatch", 112.1, 119.5, parent="trainer.step"),
        _jit("trace", "_step", 112.2, 115.2, parent="trainer.dispatch"),
        _jit("trace", "_where", 112.5, 113.0, parent="trainer.dispatch"),
        # an eager operation inside the step's trace gets its executable
        _jit("lower", "jit(iota)", 113.5, 113.6, parent="trainer.dispatch"),
        _jit("compile", "jit(iota)", 113.6, 114.0, cache="hit",
             retrieval_s=0.3, saved_s=0.0, parent="trainer.dispatch"),
        _jit("lower", "jit(_step)", 115.2, 116.2, parent="trainer.dispatch"),
        _jit("compile", "jit(_step)", 116.2, 118.7, cache="hit",
             retrieval_s=2.0, saved_s=60.0, parent="trainer.dispatch"),
        _span("trainer.device_step", 112.1, 119.8, step=1, after="dispatch"),
        # a feed's thread compiles while step 1 is dispatched
        _jit("compile", "jit(stage)", 118.8, 119.3, tid=7, cache="off"),
        # another process's
        _jit("compile", "jit(probe)", 113.0, 114.5, pid=3, cache="off"),
        # the output check's, between step 1 and the window
        _jit("trace", "norms", 120.6, 121.0),
        _jit("compile", "jit(norms)", 121.0, 123.0, cache="miss",
             entry_bytes=4096, written=1),
        _span("trainer.step", 124.0, 124.1, step=2),
        _span("trainer.dispatch", 124.0, 124.05, parent="trainer.step"),
        _span("trainer.device_step", 124.0, 124.1, step=2, after="dispatch"),
        # inside the window: no metric's, whatever the check makes of it
        _jit("trace", "late", 131.0, 132.0),
    ]


def _run(tmp_path, monkeypatch, events, dropped=0, counters=True):
    monkeypatch.setattr(ps, "ROOT", str(tmp_path))
    obs_dir = tmp_path / ".benchmark_out" / "cell" / "scratch" / "app" / "obs"
    obs_dir.mkdir(parents=True)
    (obs_dir / "trace.json").write_text(json.dumps(
        {"traceEvents": events, "tfos": {"dropped": {"worker:0": dropped}}}))
    if counters:
        (obs_dir / "counters.json").write_text(json.dumps({
            "driver:11": {"counters": {
                "spark_partition_batches_sent_total": 8}},
            "worker:0:12": {"counters": {}},
            "worker:0:13": {"counters": {
                "jit_traces_total": 3170.0,
                "compile_cache_disk_misses_total": 1.0}}}))
    return {"cell": {"name": "cell"}, "notes": [], "t_launch": 90.0,
            "driver": {"t_cluster_run": 92.0},
            "trainer": {"t_map_fun": 95.0, "t_first_step_done": 120.0,
                        "t_window_start": 130.0,
                        "window": {"seconds": 10.0}}}


def test_benchmark_step_one_splits_into_four_that_add_up(tmp_path,
                                                         monkeypatch):
    run = _run(tmp_path, monkeypatch, _events())
    # the eager operation's lowering and load are theirs, not the trace's
    assert _read("first_step_trace_s", run) == pytest.approx(3.0 - 0.1 - 0.4)
    assert _read("first_step_lower_s", run) == pytest.approx(1.0 + 0.1)
    assert _read("first_step_load_s", run) == pytest.approx(2.5 + 0.4)
    wall = 119.8 - 112.1
    assert _read("first_step_run_s", run) == pytest.approx(
        wall - 2.5 - 1.1 - 2.9)
    assert sum(_read(n, run) for n in list(NEW)[:4]) == pytest.approx(wall)
    step = start_spans.start(run)["first_step"]
    assert step["wall_s"] == pytest.approx(wall)
    assert step["dispatch_s"] == pytest.approx(119.5 - 112.1)
    # the hits' reads lie inside the load
    assert step["retrieval_s"] == pytest.approx(2.3) and (
        step["retrieval_s"] <= step["load_s"])


def test_benchmark_init_and_setup_are_unions_of_the_trainers_spans(
        tmp_path, monkeypatch):
    run = _run(tmp_path, monkeypatch, _events())
    # the nested trace once: [100.5, 104.0]
    assert _read("init_jit_s", run) == pytest.approx(3.5)
    # + step 1's [112.2, 118.7], the feed thread's 0.5 s, the check's
    # [120.6, 123.0]; not another process's, not the window's
    assert _read("setup_jit_s", run) == pytest.approx(3.5 + 6.5 + 0.5 + 2.4)
    found = start_spans.start(run)
    assert found["init"]["trace_s"] == pytest.approx(1.0)
    assert found["init"]["wall_s"] == pytest.approx(10.0)
    assert found["setup"]["compile_s"] == pytest.approx(
        2.0 + 0.4 + 2.5 + 0.5 + 2.0)
    assert found["spans"] == {"jit.trace": 6, "jit.lower": 3,
                              "jit.compile": 5}
    assert _read("jit_traces", run) == 3170
    assert _read("cache_disk_misses", run) == 1
    assert isinstance(_read("jit_traces", run), int)


def test_benchmark_start_spans_json_names_what_the_metrics_leave_out(
        tmp_path, monkeypatch):
    run = _run(tmp_path, monkeypatch, _events())
    assert _read("setup_jit_s", run) is not None
    with open(tmp_path / ".benchmark_out" / "cell" / "start_spans.json") as f:
        out = json.load(f)
    # self time: a trace less the recorded spans it holds on its thread
    by_fun = {r["fun"]: r for r in out["trace_self"]}
    assert [r["fun"] for r in out["trace_self"]] == [
        "_step", "_init", "_where", "norms", "_uniform"]
    assert by_fun["_step"]["self_s"] == pytest.approx(3.0 - 0.5 - 0.1 - 0.4)
    assert by_fun["_step"]["total_s"] == pytest.approx(3.0)
    assert by_fun["_init"]["self_s"] == pytest.approx(0.7)
    # every compile of the trainer's process that was no hit
    assert [(r["fun"], r["cache"], r["entry_bytes"], r["written"])
            for r in out["not_hit"]] == [
        ("jit(stage)", "off", None, None), ("jit(norms)", "miss", 4096, 1)]
    assert out["not_hit"][1]["seconds"] == pytest.approx(2.0)
    assert out["not_hit"][1]["after_launch_s"] == pytest.approx(31.0)
    # [t_map_fun, t_first_step_done] under no span of the stepping thread:
    # before Trainer(), and between it and step 1 (0.1 s after step 1 is
    # below the floor; node.map_fun holds the whole stretch)
    assert out["uncovered"] == [pytest.approx([0.0, 5.0]),
                                pytest.approx([15.0, 17.0])]
    (note,) = [n for n in run["notes"] if n.startswith("start spans:")]
    assert "14 jit.* spans" in note and "jit(norms) (miss, 2.000 s" in note
    assert "2.500 / 1.100 / 2.900 / 1.200 s of 7.700 s" in note


@pytest.mark.parametrize("name", NEW)
def test_benchmark_start_span_metric_is_left_out_where_it_cannot_be_whole(
        name, tmp_path, monkeypatch):
    whole = _run(tmp_path / "a", monkeypatch, _events())
    assert _read(name, whole) is not None
    partial = _run(tmp_path / "b", monkeypatch, _events(), dropped=2)
    assert _read(name, partial) is None
    # the parent of PR 53: the trainer's spans and counters, no jit.compile
    # — a counter never touched would read 0, and must not
    parent = _run(tmp_path / "c", monkeypatch, [
        e for e in _events() if not e["name"].startswith("jit.")])
    assert _read(name, parent) is None
    traces_only = _run(tmp_path / "d", monkeypatch, [
        e for e in _events() if e["name"] != "jit.compile"])
    assert _read(name, traces_only) is None
    monkeypatch.setattr(ps, "ROOT", str(tmp_path / "none"))
    bare = {"cell": {"name": "cell"}, "notes": [], "t_launch": 90.0,
            "trainer": {"t_window_start": 100.0,
                        "window": {"seconds": 10.0}}}
    assert _read(name, bare) is None and bare["notes"] == []


def test_benchmark_a_step_without_its_device_span_keeps_the_three_phases(
        tmp_path, monkeypatch):
    """The watcher's full queue turned step 1 away: its wall is unknown, the
    spans under its dispatch are not."""
    run = _run(tmp_path, monkeypatch, [
        e for e in _events() if not (e["name"] == "trainer.device_step"
                                     and e["args"]["step"] == 1)])
    assert _read("first_step_run_s", run) is None
    assert _read("first_step_load_s", run) == pytest.approx(2.9)
    assert _read("setup_jit_s", run) == pytest.approx(12.9)


# -- a fixture cell, end to end on the CPU ------------------------------------


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("start_spans_tree")
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(OVERLAY, root, dirs_exist_ok=True)
    with open(root / "BENCHMARK.json") as f:
        fixture = json.load(f)
    real = {m["name"]: m for m in spec.load(REPO)["per_layer"]}
    for name in NEW:
        fixture["per_layer"].append(dict(real[name],
                                         workloads=list(FIXTURE_CELLS)))
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(fixture, f)
    return root


def test_benchmark_fixture_cell_reports_its_start_by_phase(tree):
    workload = "tiny_spark"
    env = dict(os.environ, JAX_PLATFORMS="cpu", TFOS_COMPILE_CACHE="0",
               TFOS_HOST_DEVICE_COUNT="1", PYTHONPATH=REPO,
               TFOS_FEED_SHM="0")
    for inherited in ("TFOS_NUM_CHIPS", "XLA_FLAGS", "TFOS_TRACE"):
        env.pop(inherited, None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "fixtures", "run_cell.py"),
         str(tree), workload, str(2 ** 31 + 5353), "2", "1"],
        capture_output=True, text=True, timeout=600, env=env, cwd=str(tree))
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    lines = proc.stdout.strip().splitlines()
    metrics = json.loads(lines[-1])["metrics"]
    for name in NEW:
        assert name in metrics, (name, lines[-12:])
        assert metrics[name]["unit"] == NEW[name][0]
    got = {name: metrics[name]["value"] for name in NEW}
    assert all(got[name] > 0 for name in list(NEW)[:7]), got
    assert got["cache_disk_misses"] == 0         # the cache is off
    with open(tree / ".benchmark_out" / workload / "start_spans.json") as f:
        out = json.load(f)
    step = out["first_step"]
    assert sum(got[n] for n in list(NEW)[:4]) == pytest.approx(
        step["wall_s"], abs=1e-6)
    assert step["wall_s"] <= metrics["trainer_ready_s"]["value"]
    assert got["init_jit_s"] <= out["init"]["wall_s"]
    assert got["init_jit_s"] + step["wall_s"] - got["first_step_run_s"] <= (
        got["setup_jit_s"] + 1e-6)
    # every trace is counted, a span only for the long ones
    assert got["jit_traces"] > out["spans"]["jit.trace"] > 0
    assert "_step" in {r["fun"] for r in out["trace_self"]}
    assert out["not_hit"] and {r["cache"] for r in out["not_hit"]} == {"off"}
    assert "jit(_step)" in {r["fun"] for r in out["not_hit"]}
    (trace_path,) = (tree / ".benchmark_out" / workload).glob(
        "scratch/*/obs/trace.json")
    with open(trace_path) as f:
        trace = json.load(f)
    assert sum(trace["tfos"]["dropped"].values()) == 0
    # the window compiled nothing, so it holds no jit.* span
    with open(tree / ".benchmark_out" / workload / "program_spans.json") as f:
        inside = json.load(f)["spans"]
    assert not [name for name in inside if name.startswith("jit.")]
    assert any(line.startswith("start spans: ") for line in lines)
