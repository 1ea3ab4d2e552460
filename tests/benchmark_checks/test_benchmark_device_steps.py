"""``benchmark/device_steps.py`` and the nine per-layer readers built on it
(PR 37): on a hand-made ring and reduced trace, and on the fixture cells end to
end on the CPU, where ``Trainer``'s completion watcher writes the spans."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import device_steps, program_spans as ps, spec, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
OVERLAY = os.path.join(HERE, "fixtures", "overlay")
NEW = ("device_idle_pct", "idle_feed_pct", "idle_h2d_pct", "idle_host_pct",
       "h2d_wait_ms", "step_enqueue_pct", "h2d_transfer_ms",
       "device_step_est_ms", "device_step_est_err_pct")
FIXTURE_CELLS = ("tiny_fed_4chip", "tiny_spark")
OFFSET = -50.0          # the profiler's clock minus time.time()


def _read(name, run):
    return spec.module("benchmark", "metrics", name).read(run)


def test_benchmark_device_step_modules_import_no_jax():
    modules = ", ".join(["benchmark.device_steps"]
                        + [f"benchmark.metrics.{m}" for m in NEW])
    code = ("import sys; sys.path.insert(0, %r); import %s; "
            "bad = [m for m in sys.modules if m == 'jax' or "
            "m.startswith(('jax.', 'jaxlib'))]; assert not bad, bad"
            % (REPO, modules))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


@pytest.mark.parametrize("name", NEW)
def test_benchmark_device_step_metric_is_declared_in_every_cell(name):
    spec_ = spec.load(REPO)
    (entry,) = [m for m in spec_["per_layer"] if m["name"] == name]
    assert entry["source"] == "program_span" and entry["better"] == "lower"
    assert entry["moves"] == "examples_per_s_chip"
    # host clock reads, however much they say about the device
    assert entry["layer"] == "trainer"
    assert entry["workloads"] == [w["name"] for w in spec_["workloads"]]
    assert entry["unit"] == ("ms" if name.endswith("_ms") else "%")
    reader = spec.module("benchmark", "metrics", name)
    assert callable(reader.read) and reader.__doc__


# -- a hand-made ring and reduced trace ---------------------------------------


def _span(name, t0, dur, pid=2, **args):
    return {"name": name, "ph": "X", "ts": t0 * 1e6, "dur": dur * 1e6,
            "pid": pid, "tid": 1, "args": args}


def _end(step):
    return 100.0 + step - 0.05


def _events():
    """Nine steps a second apart inside the window [100, 110]: the device
    busy 0.6 s a step (0.9 in steps 5–7), the consumer in ``feed.wait`` for
    the first 0.1 s of every gap, the batch 0.15 s late in the even steps, and
    a turn-round over the last 0.05 s of step 4's gap."""
    events = []
    for step in range(1, 10):
        busy = 0.9 if step in (5, 6, 7) else 0.6
        late = step % 2 == 0
        events.append(_span(
            "trainer.device_step", _end(step) - busy, busy, step=step,
            after="input" if late else "dispatch",
            input_wait_s=0.15 if late else 0.0, dispatch_s=0.002))
        events.append(_span("trainer.step", _end(step) - busy - 0.01, 0.02,
                            step=step))
        events.append(_span("feed.wait", _end(step), 0.1))
        events.append(_span("trainer.h2d", _end(step) - 0.3, 0.08, bytes=4096))
    events.append(_span("feed.turnround", _end(4) - 0.6 - 0.05, 0.25))
    return events


def _run(tmp_path, monkeypatch, events, dropped=0, profiled=()):
    monkeypatch.setattr(ps, "ROOT", str(tmp_path))
    obs_dir = tmp_path / ".benchmark_out" / "cell" / "scratch" / "app" / "obs"
    obs_dir.mkdir(parents=True)
    (obs_dir / "trace.json").write_text(json.dumps(
        {"traceEvents": events, "tfos": {"dropped": {"worker:0": dropped}}}))
    run = {"cell": {"name": "cell"}, "notes": [], "t_launch": 90.0,
           "driver": {"t_cluster_run": 95.0},
           "trainer": {"t_map_fun": 99.0, "t_window_start": 100.0,
                       "window": {"seconds": 10.0}}}
    if profiled:
        # what ``program_spans.traced`` leaves behind: the session saw these
        # steps; its window runs from inside the first to inside the last;
        # the device was busy exactly under their ``trainer.device_step``s
        lo = _end(profiled[0]) - 0.5 + OFFSET
        hi = _end(profiled[-1]) - 0.2 + OFFSET
        busy = [(_end(s) - 0.9 + OFFSET, _end(s) + OFFSET) for s in profiled]
        run["_traced"] = {
            "window": [lo, hi], "steps": len(profiled),
            "step_starts": {str(s): _end(s) - 0.91 + OFFSET
                            for s in profiled},
            "idle_gaps": [trace_reduce.gaps(
                trace_reduce.clip(busy, lo, hi), lo, hi)],
            "host_spans": {}, "clock": {"offset_s": OFFSET, "spread_s": 0.0,
                                        "pairs": len(profiled)}}
    return run


def test_benchmark_idle_split_sums_to_the_idle_share(tmp_path, monkeypatch):
    run = _run(tmp_path, monkeypatch, _events(), profiled=(5, 6))
    # untraced with a predecessor: steps 2, 3, 4, 8, 9 — five periods of 1 s
    assert [r["step"] for r in device_steps.untraced(run)] == [2, 3, 4, 8, 9]
    assert _read("device_idle_pct", run) == pytest.approx(40.0)
    assert _read("idle_feed_pct", run) == pytest.approx(100 * 0.55 / 5.0)
    assert _read("idle_h2d_pct", run) == pytest.approx(100 * 0.40 / 5.0)
    assert _read("idle_host_pct", run) == pytest.approx(100 * 1.05 / 5.0)
    assert (_read("idle_feed_pct", run) + _read("idle_h2d_pct", run)
            + _read("idle_host_pct", run)) == pytest.approx(
                _read("device_idle_pct", run), abs=1e-9)
    assert _read("h2d_wait_ms", run) == pytest.approx(150.0)
    assert _read("device_step_est_ms", run) == pytest.approx(600.0)
    # steps 3 and 9 began at their dispatch's start: the bracket's width
    assert _read("step_enqueue_pct", run) == pytest.approx(
        100 * 2 * 0.002 / 5.0)
    # the transfers of steps 5-7 lie under the session: 80 ms all the same
    assert _read("h2d_transfer_ms", run) == pytest.approx(80.0)


def test_benchmark_traced_steps_and_the_one_after_are_left_out(
        tmp_path, monkeypatch):
    """Steps 5–7 are busy 0.9 s of their second: a run that saw them traced
    leaves them (and the write-out's step) out, one with no trace does not."""
    traced = _run(tmp_path / "a", monkeypatch, _events(), profiled=(5, 6))
    assert _read("device_idle_pct", traced) == pytest.approx(40.0)
    plain = _run(tmp_path / "b", monkeypatch, _events())
    assert [r["step"] for r in device_steps.untraced(plain)] == list(
        range(2, 10))
    assert _read("device_idle_pct", plain) == pytest.approx(
        100 * (5 * 0.4 + 3 * 0.1) / 8.0)
    assert sorted(r["dur_s"] for r in device_steps.untraced(plain))[-3:] == [
        pytest.approx(0.9)] * 3
    assert _read("device_step_est_err_pct", plain) is None
    with open(tmp_path / "a" / ".benchmark_out" / "cell" /
              "device_steps.json") as f:
        summary = json.load(f)
    assert summary["profiled_steps"] == [5, 6, 7]
    assert summary["untraced_steps"] == 5
    assert summary["device_step_ms"] == {"untraced": pytest.approx(600.0),
                                         "profiled": pytest.approx(900.0)}
    assert summary["h2d_ms"]["profiled_count"] == 3
    assert summary["h2d_ms"]["untraced_count"] == 6
    assert summary["after"] == {"dispatch": 3, "input": 3}
    assert summary["enqueue_pct"] == pytest.approx(100 * 2 * 0.002 / 5.0)
    assert any(n.startswith("device steps: 5 untraced, 3 under the profiler")
               for n in traced["notes"])


def test_benchmark_transfers_under_the_session_are_kept_apart(
        tmp_path, monkeypatch):
    """The profiler slows a transfer: ``h2d_transfer_ms`` reads the batches
    staged outside its session, ``device_steps.json`` holds both."""
    events = _events()
    for e in events:
        if e["name"] == "trainer.h2d" and _end(5) - 1 < e["ts"] * 1e-6 < _end(7):
            e["dur"] = 0.25 * 1e6
    traced = _run(tmp_path / "a", monkeypatch, events, profiled=(5, 6))
    outside, under = device_steps.transfers(traced)
    assert (len(outside), len(under)) == (6, 3)
    assert _read("h2d_transfer_ms", traced) == pytest.approx(80.0)
    assert _read("device_idle_pct", traced) == pytest.approx(40.0)
    with open(tmp_path / "a" / ".benchmark_out" / "cell" /
              "device_steps.json") as f:
        assert json.load(f)["h2d_ms"] == {
            "untraced": pytest.approx(80.0), "untraced_count": 6,
            "profiled": pytest.approx(250.0), "profiled_count": 3}
    plain = _run(tmp_path / "b", monkeypatch, events)
    outside, under = device_steps.transfers(plain)
    assert (len(outside), len(under)) == (9, 0)


def test_benchmark_estimate_error_is_zero_on_its_own_intervals(
        tmp_path, monkeypatch):
    run = _run(tmp_path, monkeypatch, _events(), profiled=(5, 6))
    assert _read("device_step_est_err_pct", run) == pytest.approx(
        0.0, abs=1e-6)
    # a device a tenth less busy than the estimate says
    (gaps,) = run["_traced"]["idle_gaps"]
    lo, hi = run["_traced"]["window"]
    busy = (hi - lo) - sum(e - s for s, e in gaps)
    s, e = gaps[0]
    run["_traced"]["idle_gaps"] = [[(s, e + 0.1 * busy)] + gaps[1:]]
    assert _read("device_step_est_err_pct", run) == pytest.approx(
        100 * 0.1 / 0.9)


@pytest.mark.parametrize("name", NEW)
def test_benchmark_dropped_events_leave_the_metric_out(
        name, tmp_path, monkeypatch):
    whole = _run(tmp_path / "a", monkeypatch, _events(), profiled=(5, 6))
    assert _read(name, whole) is not None
    partial = _run(tmp_path / "b", monkeypatch, _events(), dropped=2,
                   profiled=(5, 6))
    assert _read(name, partial) is None


@pytest.mark.parametrize("name", NEW)
def test_benchmark_a_parent_without_the_spans_leaves_the_metric_out(
        name, tmp_path, monkeypatch):
    """The program before PR 37 writes a ring with no ``trainer.device_step``;
    the program before PR 24 writes none at all."""
    others = [e for e in _events() if not e["name"].startswith(
        ("trainer.device_step", "trainer.h2d"))]
    run = _run(tmp_path / "a", monkeypatch, others, profiled=(5, 6))
    assert _read(name, run) is None
    monkeypatch.setattr(ps, "ROOT", str(tmp_path / "none"))
    bare = {"cell": {"name": "cell"}, "notes": [], "trainer": {
        "t_window_start": 100.0, "window": {"seconds": 10.0}}}
    assert _read(name, bare) is None and bare["notes"] == []


def test_benchmark_a_missing_step_breaks_the_chain_not_the_reader(
        tmp_path, monkeypatch):
    """A step the watcher's full queue turned away has no span: the step
    after it has no period, the others keep theirs."""
    events = [e for e in _events()
              if not (e["name"] == "trainer.device_step"
                      and e["args"]["step"] == 3)]
    run = _run(tmp_path, monkeypatch, events)
    assert [r["step"] for r in device_steps.untraced(run)] == [
        2, 5, 6, 7, 8, 9]


# -- the fixture cells, end to end on the CPU ---------------------------------


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("device_steps_tree")
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


@pytest.mark.parametrize("workload, devices", [("tiny_spark", 1),
                                               ("tiny_fed_4chip", 4)])
def test_benchmark_fixture_cell_reports_the_device_steps(tree, workload,
                                                         devices):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TFOS_COMPILE_CACHE="0",
               TFOS_HOST_DEVICE_COUNT=str(devices), PYTHONPATH=REPO,
               TFOS_FEED_SHM="0")
    for inherited in ("TFOS_NUM_CHIPS", "XLA_FLAGS", "TFOS_TRACE"):
        env.pop(inherited, None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "fixtures", "run_cell.py"),
         str(tree), workload, str(2 ** 31 + 3737), "2", "1"],
        capture_output=True, text=True, timeout=600, env=env, cwd=str(tree))
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    lines = proc.stdout.strip().splitlines()
    metrics = json.loads(lines[-1])["metrics"]
    # the CPU rehearsal has no device plane, so no profiler to hold the
    # estimate against: eight of the nine
    for name in NEW[:-1]:
        assert name in metrics, (name, lines[-12:])
    assert "device_step_est_err_pct" not in metrics
    split = sum(metrics[n]["value"] for n in NEW[1:4])
    assert split == pytest.approx(metrics["device_idle_pct"]["value"],
                                  abs=1e-6)
    assert 0 <= metrics["device_idle_pct"]["value"] <= 100
    assert all(metrics[n]["value"] >= 0 for n in NEW[:-1])
    assert metrics["device_step_est_ms"]["value"] > 0
    out = tree / ".benchmark_out" / workload
    (trace_path,) = out.glob("scratch/*/obs/trace.json")
    with open(trace_path) as f:
        trace = json.load(f)
    assert sum(trace["tfos"]["dropped"].values()) == 0
    spans = [ev for ev in trace["traceEvents"] if ev["ph"] == "X"]
    steps = sorted((ev for ev in spans if ev["name"] == "trainer.step"),
                   key=lambda ev: ev["ts"])
    device = sorted((ev for ev in spans
                     if ev["name"] == "trainer.device_step"),
                    key=lambda ev: ev["ts"])
    assert [ev["args"]["step"] for ev in device] == [
        ev["args"]["step"] for ev in steps]
    # every batch came staged by the feed's Trainer.shard: one transfer each
    transfers = [ev for ev in spans if ev["name"] == "trainer.h2d"]
    assert len(transfers) >= len(steps)
    assert all(ev["args"]["bytes"] > 0 for ev in transfers)
    with open(out / "device_steps.json") as f:
        assert json.load(f)["untraced_steps"] == len(
            device_steps_in_window(out, device)) - 1


def device_steps_in_window(out, device):
    with open(out / "trainer_report.json") as f:
        report = json.load(f)
    lo = report["t_window_start"] * 1e6
    hi = lo + report["window"]["seconds"] * 1e6
    return [ev for ev in device if ev["ts"] >= lo
            and ev["ts"] + ev["dur"] <= hi]
