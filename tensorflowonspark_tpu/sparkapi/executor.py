"""Executor process main loop for the local Spark substrate.

One instance of :func:`executor_main` runs per executor process.  It mirrors
what a Spark executor's python worker does with a task: deserialize the
function chain, apply it to the partition iterator — which unpickles the
partition's rows one batch at a time, as the task consumes them — and ship
the result (or the traceback) back to the driver.

Each executor gets its own working directory under the app scratch dir —
this preserves the reference's executor-id collision-guard semantics
(``tensorflowonspark/util.py::write_executor_id`` writes to the executor's
cwd, which Spark keeps distinct per executor).
"""

from __future__ import annotations

import os
import pickle
import time
import traceback
from typing import Any, Iterator


def _rows(blobs: list[bytes]) -> Iterator[Any]:
    """The task's iterator over a partition sent as row-batch pickles: a
    batch is unpickled when the one before it is exhausted, so the task's
    function sees its first row after one batch's load, and a consumer that
    holds the task back (a full feed queue) holds the unpickling back too."""
    for blob in blobs:
        yield from pickle.loads(blob)


def executor_main(executor_id: int, app_id: str, task_queue, result_queue,
                  started_at: float | None = None) -> None:
    import queue as queue_mod

    import cloudpickle

    from tensorflowonspark_tpu import obs, util

    if started_at is not None:
        # the driver's Process.start() to this line, on the host's one
        # wall clock: interpreter start and the package's imports
        obs.complete("executor.start", started_at, time.time() - started_at,
                     executor_id=executor_id)
    wd = os.path.join(util.single_node_scratch_dir(app_id), f"executor_{executor_id}")
    os.makedirs(wd, exist_ok=True)
    os.chdir(wd)
    os.environ["TFOS_APP_ID"] = app_id
    driver_pid = os.getppid()

    while True:
        try:
            item = task_queue.get(timeout=5.0)
        except queue_mod.Empty:
            # executors are non-daemonic (they must spawn the manager and
            # trainer); if the driver died without running stop()/atexit
            # (SIGKILL, os._exit), exit instead of lingering forever
            if os.getppid() != driver_pid:
                break
            continue
        if item is None:
            break
        job_id, task_id, pindex, blobs, chain_blob = item
        # one span a task; its first child loads the function chain and
        # opens the row stream.  The rows themselves are unpickled under
        # the task's iterator, where Spark has that cost too (TFSparkNode's
        # `feeder.first_row` sees the first batch's)
        with obs.span("executor.task", job=job_id, partition=pindex):
            try:
                with obs.span("executor.task_load",
                              bytes=sum(map(len, blobs)), chunks=len(blobs)):
                    chain, action = cloudpickle.loads(chain_blob)
                    it = _rows(blobs)
                for f in chain:
                    it = f(pindex, it)
                result = action(pindex, it)
                result_queue.put(
                    (job_id, task_id, True, cloudpickle.dumps(result)))
            except BaseException:
                result_queue.put(
                    (job_id, task_id, False, traceback.format_exc()))
