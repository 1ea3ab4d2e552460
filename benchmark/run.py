"""The benchmark's command: one run of one cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process is a launcher and never imports JAX: a parent that has touched
JAX holds the chip, and the trainer that needs it then fails.  It makes the
cell's data from the seed, starts the driver program (``driver.py``), which
runs the cell through ``TFCluster`` exactly as a user's job does, reads what
the driver and the trainer wrote, decides ``correct``, and prints the
contract's one last line.  A run with no chip fails: there is no fallback.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time

T_LAUNCH = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check, peaks, spec, stats  # noqa: E402
from benchmark.peaks import NoAcceleratorError  # noqa: E402

#: exit codes (2 and 3 are the chip tool's own)
EXIT_NO_ACCELERATOR = 4
EXIT_RUN_FAILED = 5
#: a cell's first run in a checkout compiles; the contract allows it 1200 s
DRIVER_TIMEOUT_S = 1100.0
OUT_DIR = ".benchmark_out"
CACHE_DIR = ".jax_cache"


def look_for_chips(chips: int) -> None:
    """Refuse to start without the chips, JAX-free: the platform selector
    must allow the TPU and the host must show the chips' device nodes.  The
    trainer checks again, from inside JAX, what it really got."""
    from tensorflowonspark_tpu import chip_info

    platforms = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if platforms and "tpu" not in platforms.split(","):
        raise NoAcceleratorError(
            f"JAX_PLATFORMS={platforms!r} keeps JAX off the TPU: no "
            "accelerator, no run")
    override = os.environ.pop("TFOS_NUM_CHIPS", None)
    try:
        found = chip_info.get_num_host_chips()
    finally:
        if override is not None:
            os.environ["TFOS_NUM_CHIPS"] = override
    if found < chips:
        raise NoAcceleratorError(
            f"the cell asks for {chips} chip(s), this host shows {found} "
            "TPU device node(s): no accelerator, no run")


def make_plan(cell: dict, args, out_dir: str, require_chip: bool) -> dict:
    traffic = cell["traffic_values"]
    generator = spec.module(cell["package"], "traffic", traffic["generator"])
    data = generator.generate(traffic, args.seed, os.path.join(out_dir, "data"))
    return {
        "root": ROOT, "out_dir": out_dir, "workload": cell["name"],
        "package": cell["package"], "config_package": cell["config_package"],
        "config": cell["config_values"], "traffic": traffic,
        "chips": cell["chips"], "require_chip": require_chip,
        "claim_chips": cell["chips"] if require_chip else 0,
        "batch": traffic["batch_per_chip"] * cell["chips"],
        "seed": args.seed, "seconds": args.seconds, "trace": args.trace,
        "data": data, "timeout_s": DRIVER_TIMEOUT_S,
        "t_launch": T_LAUNCH, "t_data_made": time.time(),
    }


def start_driver(plan: dict, env_extra: dict) -> dict:
    """Run the driver program to its end in a session of its own, so that
    everything it started can be stopped with it; return both reports."""
    out_dir = plan["out_dir"]
    plan_path = os.path.join(out_dir, "plan.json")
    with open(plan_path, "w", encoding="utf-8") as f:
        json.dump(plan, f)
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, CACHE_DIR))
    env["TFOS_SCRATCH_ROOT"] = os.path.join(out_dir, "scratch")
    env["TFOS_NUM_CHIPS"] = str(plan["claim_chips"])
    env.update(env_extra)
    os.makedirs(env["TFOS_SCRATCH_ROOT"], exist_ok=True)
    with open(os.path.join(out_dir, "driver.stdout"), "w") as out, \
            open(os.path.join(out_dir, "driver.stderr"), "w") as err:
        proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "driver.py"), plan_path],
            stdout=out, stderr=err, cwd=ROOT, env=env, start_new_session=True)
        try:
            proc.wait(timeout=plan["timeout_s"] + 60)
        except subprocess.TimeoutExpired:
            pass
        finally:
            stop_group(proc)
    reports = {"driver_exit": proc.returncode}
    for name in ("driver", "trainer"):
        path = os.path.join(out_dir, f"{name}_report.json")
        if os.path.isfile(path):
            with open(path, encoding="utf-8") as f:
                reports[name] = json.load(f)
    return reports


def stop_group(proc) -> None:
    """Stop the driver and whatever it left behind (its session is its
    process group), and wait until the group is empty."""
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        try:
            os.killpg(proc.pid, 0)
        except ProcessLookupError:
            return
        time.sleep(0.05)


def fail(out_dir: str, why: str, code: int = EXIT_RUN_FAILED):
    err_path = os.path.join(out_dir, "driver.stderr")
    if os.path.isfile(err_path):
        with open(err_path, encoding="utf-8", errors="replace") as f:
            sys.stderr.write("---- driver stderr (tail) ----\n"
                             f"{f.read()[-8000:]}\n----\n")
    sys.stderr.write(f"benchmark: run FAILED: {why}\n")
    sys.exit(code)


def end_to_end(run: dict) -> dict:
    """The end-to-end metrics, by the launcher's and the trainer's clocks."""
    trainer, driver = run["trainer"], run["driver"]
    window = trainer["window"]
    gaps = [b - a for a, b in zip(window["step_end_s"],
                                  window["step_end_s"][1:])]
    run["step_gaps_s"] = gaps
    return {
        "setup_s": trainer["t_window_start"] - run["t_launch"],
        "examples_per_s_chip": (window["rows"] / window["seconds"]
                                / run["cell"]["chips"]),
        "step_ms_p95": 1e3 * stats.percentile(gaps, 95.0),
    }


def shutdown_seconds(run: dict) -> float:
    """Tear-down proper: from the moment both sides are finished (the driver
    has called shutdown, the trainer's work is done) to every executor gone.
    Printed with every run, not a metric: its runs spread by more than half
    of any bound the contract allows (PERF.md, PR 23)."""
    trainer, driver = run["trainer"], run["driver"]
    return driver["t_executors_down"] - max(driver["t_shutdown_called"],
                                            trainer["t_done"])


def decide_correct(run: dict, limits: dict) -> list:
    """Every number compared, beside its limit."""
    trainer, driver = run["trainer"], run["driver"]
    window, feed = trainer["window"], trainer["feed"]
    rows = check.judge(trainer["check"]["numbers"], limits)
    losses = window["losses"]
    k = max(1, min(8, len(losses) // 4))
    first = trainer["check"]["program"]["losses"][0]
    last = sum(losses[-k:]) / k if losses else math.inf

    def exact(name, value, want=0):
        rows.append({"name": name, "value": value, "limit": want,
                     "ok": value == want})

    exact("window_losses_not_finite",
          sum(1 for v in losses if not math.isfinite(v)))
    rows.append({"name": "last_loss_over_first", "value": last / first,
                 "limit": 1.0, "ok": last < first})
    exact("rows_not_accounted_for", feed["bad_rows"])
    exact("compilations_in_window", window["compilations"])
    exact("executor_exit_codes_not_zero",
          sum(1 for c in driver["executor_exit_codes"] if c != 0))
    exact("shm_segments_left", len(driver["shm_left"]))
    return rows


def per_layer(run: dict, spec_: dict) -> dict:
    out = {}
    for m in spec.metrics_of(spec_, run["cell"]["name"], "per_layer"):
        reader = spec.module(run["cell"]["package"], "metrics", m["name"])
        value = reader.read(run)
        if value is not None:       # a reader that finds nothing: left out
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(args, require_chip: bool = True,
             env_extra: dict | None = None) -> dict:
    """One run; returns the result line as a dict.  ``require_chip=False``
    is the tests' way past the look for a chip; the command never uses it."""
    spec_ = spec.load(ROOT)
    spec.validate(spec_)
    spec.validate_files(spec_)
    cell = spec.cell(spec_, args.workload)
    import tensorflowonspark_tpu  # noqa: F401 - a bare checkout fails here

    if require_chip:
        look_for_chips(cell["chips"])
    out_dir = os.path.join(ROOT, OUT_DIR, cell["name"])
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    plan = make_plan(cell, args, out_dir, require_chip)
    try:
        reports = start_driver(plan, env_extra or {})
    finally:
        shutil.rmtree(os.path.join(out_dir, "data"), ignore_errors=True)
    trainer, driver = reports.get("trainer"), reports.get("driver")
    if trainer is None or driver is None or reports["driver_exit"] != 0:
        fail(out_dir, f"driver exited {reports['driver_exit']}; reports: "
                      f"{sorted(k for k in reports if k != 'driver_exit')}"
                      + (f"; trainer error:\n{trainer['error']}"
                         if trainer and trainer.get("error") else ""))
    if trainer.get("error"):
        fail(out_dir, f"trainer error:\n{trainer['error']}")
    device = trainer["device"]
    if require_chip:
        if device["platform"] != "tpu" or device["local_count"] < cell["chips"]:
            fail(out_dir, f"the trainer ran on {device}", EXIT_NO_ACCELERATOR)
        run_peaks = peaks.peaks_for(device["kind"])
    else:
        run_peaks = peaks.PEAKS.get(device["kind"])
    work = spec.module(cell["config_package"], "work").step_work(
        cell["config_values"], plan["batch"])
    run = {"cell": cell, "trainer": trainer, "driver": driver, "work": work,
           "peaks": run_peaks, "t_launch": T_LAUNCH, "notes": []}
    if len(trainer["window"]["step_end_s"]) < 2:
        fail(out_dir, f"{trainer['window']['steps']} steps completed in the "
                      "window: nothing to measure")

    e2e = end_to_end(run)
    limits_path = os.path.join(ROOT, os.path.dirname(
        cell["config_entry"]["file"]), "limits.json")
    with open(limits_path, encoding="utf-8") as f:
        limits = json.load(f)["limits"]
    compared = decide_correct(run, limits)
    if args.trace:
        metrics = per_layer(run, spec_)
    else:
        units = {m["name"]: m["unit"] for m in spec.metrics_of(
            spec_, cell["name"], "end_to_end")}
        metrics = {k: {"value": v, "unit": units[k]} for k, v in e2e.items()
                   if k in units}
    for row in compared:
        print(f"compared {row['name']}: {row['value']} (limit {row['limit']})"
              f" {'ok' if row['ok'] else 'NOT OK'}")
    for note in run["notes"]:
        print(note)
    gaps = run["step_gaps_s"]
    print(f"step_ms: {len(gaps)} samples, median "
          f"{1e3 * stats.median(gaps):.4f} ms, "
          f"{stats.samples_beyond(len(gaps), 95.0)} beyond the 95th "
          f"percentile; shutdown {shutdown_seconds(run):.3f} s (not gated); "
          f"check took {trainer['check']['seconds']:.1f} s; "
          f"memory {json.dumps(trainer['memory'])}")
    result = {
        "correct": all(row["ok"] for row in compared),
        "attempted": trainer["window"]["steps"],
        "failed": 0,
        "metrics": metrics,
        "device": {"platform": device["platform"], "kind": device["kind"],
                   "count": device["count"],
                   "memory_peak_bytes": trainer["memory"]["memory_peak_bytes"]},
    }
    trace = trainer.get("trace")
    if args.trace and trace:
        result["device"].update(busy_s=trace["busy_s"],
                                window_s=trace["window_s"])
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    with open(os.path.join(out_dir, "result.json"), "w") as f:
        json.dump({"result": result, "end_to_end": e2e,
                   "compared": compared}, f)
    return result


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> None:
    args = parse_args(argv)
    try:
        result = run_cell(args)
    except NoAcceleratorError as e:
        sys.stderr.write(f"benchmark: NoAcceleratorError: {e}\n")
        sys.exit(EXIT_NO_ACCELERATOR)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
