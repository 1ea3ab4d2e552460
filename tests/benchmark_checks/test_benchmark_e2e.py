"""The harness end to end on the CPU, past its look for a chip, on fixture
cells that are added to a copy of ``benchmark/`` as files of their own — a
configuration directory, traffic files, a per-layer metric, a ``chips: 4``
cell — and entries in ``BENCHMARK.json``, with no edit to any file that was
there.  One run drives the cluster, the feed plane, the window, the trace and
the output check; one has the timed path broken underneath and has to come
out not correct."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
OVERLAY = os.path.join(HERE, "fixtures", "overlay")


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("benchmark_tree")
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: os.path.getmtime(os.path.join(dp, p))
              for dp, _, fs in os.walk(root / "benchmark") for p in fs}
    for dirpath, _dirs, files in os.walk(OVERLAY):
        for name in files:
            src = os.path.join(dirpath, name)
            dst = root / os.path.relpath(src, OVERLAY)
            assert not dst.exists(), f"the overlay edits {dst}"
            dst.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(src, dst)
    assert before       # the copy held the harness before the overlay
    return root


def _run(tree, workload, devices, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TFOS_COMPILE_CACHE="0",
               TFOS_HOST_DEVICE_COUNT=str(devices), PYTHONPATH=REPO)
    for inherited in ("TFOS_NUM_CHIPS", "XLA_FLAGS"):   # conftest's own
        env.pop(inherited, None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "fixtures", "run_cell.py"),
         str(tree), workload, str(2 ** 31 + 4242), "2", str(trace)],
        capture_output=True, text=True, timeout=600, env=env, cwd=str(tree))
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]), lines[:-1]


def test_benchmark_four_chip_fixture_cell_runs_on_four_virtual_devices(tree):
    result, lines = _run(tree, "tiny_fed_4chip", devices=4, trace=1)
    assert result["correct"] is True, lines
    assert result["device"]["count"] == 4
    assert result["attempted"] > 10 and result["failed"] == 0
    metrics = result["metrics"]
    # the fixture's own metric file was found by name; the readers of the
    # device trace find no device plane on the CPU and are left out
    assert metrics["window_steps"]["value"] == result["attempted"]
    for name in ("bootstrap_s", "trainer_ready_s", "feed_wait_ms",
                 "feed_bytes_per_s", "step_host_ms", "cache_disk_hits"):
        assert name in metrics, name
    assert "step_device_ms" not in metrics
    assert "setup_s" not in metrics         # --trace 1: per-layer only
    compared = [ln for ln in lines if ln.startswith("compared ")]
    assert len(compared) == 11 and all(ln.endswith(" ok") for ln in compared)


def test_benchmark_spark_fixture_cell_accounts_for_its_rows(tree):
    result, lines = _run(tree, "tiny_spark", devices=1, trace=0)
    assert result["correct"] is True, lines
    assert set(result["metrics"]) == {"setup_s", "examples_per_s_chip",
                                      "step_ms_p95"}
    assert any("shutdown" in ln and "not gated" in ln for ln in lines)
    assert all(m["value"] > 0 for m in result["metrics"].values())
    with open(tree / ".benchmark_out" / "tiny_spark" /
              "trainer_report.json") as f:
        feed = json.load(f)["feed"]
    assert feed["bad_rows"] == 0 and feed["rows_taken"] > 0
    assert feed["short_batches"] > 0        # partition ends, dropped and counted
    assert feed["rows_seen"] >= feed["rows_taken"] + feed["rows_dropped_short"]


def test_benchmark_broken_step_comes_out_not_correct(tree):
    """The fixture's program returns its state unchanged from every step."""
    result, lines = _run(tree, "tiny_broken", devices=1, trace=0)
    assert result["correct"] is False
    failed = {ln.split()[1].rstrip(":") for ln in lines
              if ln.startswith("compared ") and ln.endswith("NOT OK")}
    assert {"first_grad_norm_gap", "param_change_norm_gap"} <= failed
    assert "compilations_in_window" not in failed
