"""The driver program of one benchmark run: what a user's ``python my_job.py``
is to the framework.  Started by the launcher as a process of its own; never
imports JAX (the trainer it starts needs the chip).

    python benchmark/driver.py <out_dir>/plan.json

It starts the local Spark substrate, hands the cell's feed plane the plan
(``TFCluster.run`` or ``TFEstimator.fit`` exactly as the examples call them,
with the benchmark's ``map_fun``), stops the executors, and writes what it
saw to ``<out_dir>/driver_report.json``.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPORT = "driver_report.json"


def main(argv=None) -> None:
    (plan_path,) = (argv if argv is not None else sys.argv[1:])
    with open(plan_path, encoding="utf-8") as f:
        plan = json.load(f)
    sys.path.insert(0, plan["root"])
    from benchmark import spec, trainer_side
    from tensorflowonspark_tpu.sparkapi import LocalSparkContext

    report = {"t_driver_start": time.time(), "pid": os.getpid()}
    feed_mod = spec.module(plan["package"], "feeds", plan["traffic"]["feed"])
    sc = LocalSparkContext("local-cluster[1,1,1024]",
                           f"benchmark-{plan['workload']}")
    try:
        report.update(feed_mod.drive(plan, sc, trainer_side.map_fun))
    finally:
        report["t_stop_executors"] = time.time()
        sc.stop()
        report["t_executors_down"] = time.time()
        report["executor_exit_codes"] = [p.exitcode for p in sc._procs]
        # segments are named tfos_feed_<creator pid>_...: this run's are
        # its executors' (other jobs on the host may have their own)
        mine = tuple(f"tfos_feed_{p.pid}_" for p in sc._procs)
        report["shm_left"] = sorted(
            f for f in os.listdir("/dev/shm") if f.startswith(mine))
        path = os.path.join(plan["out_dir"], REPORT)
        with open(path + ".tmp", "w", encoding="utf-8") as f:
            json.dump(report, f)
        os.replace(path + ".tmp", path)


if __name__ == "__main__":
    main()
