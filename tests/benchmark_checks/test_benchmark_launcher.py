"""The launcher: imports no JAX, and fails by name where there is no chip."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(REPO, "benchmark", "run.py")


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("TFOS_NUM_CHIPS",)}
    env.update(extra)
    return env


def test_benchmark_launcher_and_driver_import_no_jax():
    code = ("import sys; sys.path.insert(0, %r); "
            "import benchmark.run, benchmark.driver, benchmark.spec, "
            "benchmark.check, benchmark.stats, benchmark.peaks; "
            "import benchmark.feeds.tfrecord_readers, "
            "benchmark.feeds.spark_estimator, "
            "benchmark.traffic.imagenet_records, "
            "benchmark.traffic.criteo_rows; "
            "bad = [m for m in sys.modules if m == 'jax' or "
            "m.startswith(('jax.', 'jaxlib'))]; "
            "assert not bad, bad" % REPO)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


@pytest.mark.parametrize("workload", [
    w["name"] for w in json.load(open(os.path.join(REPO, "BENCHMARK.json")))[
        "workloads"]])
def test_benchmark_run_without_a_chip_fails_by_name(workload):
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", workload, "--seed",
         str(2 ** 31 + 77), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=_env(JAX_PLATFORMS="cpu"))
    assert proc.returncode not in (0, 2, 3)
    assert "NoAcceleratorError" in proc.stderr
    assert "metrics" not in proc.stdout and proc.stdout.strip() == ""


def test_benchmark_run_without_device_nodes_fails_by_name():
    if any(os.path.basename(p).isdigit() for p in
           (os.listdir("/dev/vfio") if os.path.isdir("/dev/vfio") else [])):
        pytest.skip("this host shows TPU device nodes")
    env = _env()
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", "resnet50_fed", "--seed", "5",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env)
    assert proc.returncode not in (0, 2, 3)
    assert "NoAcceleratorError" in proc.stderr and "device node" in proc.stderr
    assert proc.stdout.strip() == ""


def test_benchmark_run_in_a_bare_directory_fails(tmp_path):
    """Only ``BENCHMARK.json`` and the files under ``paths``: no program."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in _env().items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "resnet50_fed",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=tmp_path, env=env)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "tensorflowonspark_tpu" in proc.stderr


def test_benchmark_unknown_device_kind_is_an_error():
    from benchmark import peaks

    assert peaks.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDeviceError):
        peaks.peaks_for("TPU v5")
    with pytest.raises(peaks.UnknownDeviceError):
        peaks.peaks_for("cpu")
