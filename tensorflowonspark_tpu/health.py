"""Chip/slice health probe at rendezvous.

SURVEY.md §5 (failure detection, TPU plan): "same restart-from-checkpoint
model, plus **slice-health check at rendezvous**".  The reference's only
bootstrap defense was the reservation timeout
(``tensorflowonspark/reservation.py::Client.await_reservations``) — enough
for a node that never starts, useless for a node whose accelerator is
*wedged*: a chip that accepts dispatches and never completes them registers
successfully and then hangs the whole mesh at the first collective, with
nothing shorter than ``feed_timeout`` to notice.

The probe runs a tiny jit'd matmul **in a watchdogged spawned subprocess**
and requires the bytes back on the host (``device_get``, not a readiness
ack).  A hang or crash turns into a fast,
attributed bootstrap failure: the node publishes the failure on the
rendezvous kv blackboard and raises, so the driver's
:func:`tensorflowonspark_tpu.TFCluster.run` wait loop aborts naming the sick
executor instead of timing out anonymously.

The subprocess matters twice over: it provides the watchdog (a wedged device
op cannot be interrupted in-process), and it keeps the bootstrap task's own
process free of any JAX/TPU runtime state — the trainer process must be the
first long-lived owner of the chips (SURVEY §7 hard part (a)).  One process
holds a chip at a time, and the hold ends with the process: libtpu's
``/tmp/libtpu_lockfile`` names the holder's pid and goes away on a clean
exit, a killed holder's stale file is ignored, and a fresh process
initialised the chip straight after either (measured on a v5e host, PR 21).
So the probe's ``join`` IS the release the trainer spawn waits on — no
sleep, no lock-file poll.

Env knobs:

- ``TFOS_HEALTH_PROBE`` — force-enable ("1") or disable ("0") regardless of
  chip count.  Default: probe only when real chips were claimed (a CPU-only
  bootstrap has nothing to wedge, keeping healthy-path overhead at zero).
- ``TFOS_HEALTH_PROBE_TIMEOUT_S`` — probe watchdog timeout for the
  cluster-less serving path (``pipeline.single_node_env``); the cluster
  bootstrap takes its timeout from the driver instead
  (``TFCluster.run(health_probe_timeout=…)`` via cluster_meta).
- ``TFOS_HEALTH_PROBE_HANG`` — test hook: the probe child sleeps forever,
  simulating the wedged chip (see ``tests/test_cluster.py``).
"""

from __future__ import annotations

import logging
import os
import time

logger = logging.getLogger(__name__)

DEFAULT_TIMEOUT_S = 60.0


def _probe_child() -> None:
    """Child body: touch the device and prove a matmul completes."""
    if os.environ.get("TFOS_HEALTH_PROBE_HANG"):
        time.sleep(3600)  # simulated wedge (never returns inside the watchdog)
    from tensorflowonspark_tpu import util

    util.ensure_jax_platform()
    import jax
    import jax.numpy as jnp

    x = jnp.ones((128, 128), jnp.bfloat16)
    y = jax.jit(lambda a: (a @ a).sum())(x)
    float(jax.device_get(y))  # the bytes, not an ack


def probe_chip_health(timeout_s: float = DEFAULT_TIMEOUT_S) -> str | None:
    """Run the watchdogged probe; return ``None`` if healthy, else a reason.

    Uses the *spawn* context (fork would clone any JAX threads the executor
    holds) and SIGKILLs the child on timeout — a wedged device op ignores
    gentler signals.

    The whole probe runs under an ``obs`` span (``health.probe``) carrying
    the verdict and the timeout, so a degraded run's trace shows exactly
    which phase consumed the probe window.
    """
    import multiprocessing

    from tensorflowonspark_tpu import obs

    ctx = multiprocessing.get_context("spawn")
    p = ctx.Process(target=_probe_child, name="tfos-health-probe", daemon=True)
    t0 = time.monotonic()
    with obs.span("health.probe", timeout_s=timeout_s) as sp:
        p.start()
        p.join(timeout_s)
        if p.is_alive():
            p.kill()
            p.join(5.0)
            reason = (f"device health probe hung for {timeout_s}s "
                      "(chip/slice wedged?)")
            sp.set(ok=False, reason=reason)
            return reason
        if p.exitcode != 0:
            reason = f"device health probe crashed (exit code {p.exitcode})"
            sp.set(ok=False, reason=reason)
            return reason
        sp.set(ok=True)
    logger.info("chip health probe passed in %.1fs", time.monotonic() - t0)
    return None


_STALL_EXIT_CODE = 86


class StepWatchdog:
    """Mid-training wedge detector: the rendezvous probe (above) catches a
    chip that is wedged at bootstrap, but this hardware's observed outage
    also strikes *mid-run* — a dispatched step simply never completes, and
    the mesh then hangs at a collective with nothing but ``feed_timeout``
    (driver-side, generic) to notice.  The watchdog turns that into a fast,
    attributed trainer failure: ``arm()`` when a step is dispatched,
    ``beat()`` when its result has materialized; if an armed step stays
    incomplete for ``timeout_s``, ``on_stall(reason)`` runs once (push the
    reason to the node's error queue) and then the process hard-exits
    (``os._exit``) — a wedged device op cannot be interrupted in-process,
    and failing fast is the framework's recovery contract
    (``spark.task.maxFailures=1`` semantics + restart from checkpoint,
    SURVEY §5/§7).

    ``on_stall`` is injectable so tests (and embedders that prefer a
    different policy) can observe the stall without dying.
    """

    def __init__(self, timeout_s: float, on_stall=None, *, exit_on_stall=True):
        import threading

        self.timeout_s = float(timeout_s)
        self._on_stall = on_stall
        self._exit = exit_on_stall
        self._armed_at: float | None = None
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._fired = False
        self._thread = threading.Thread(
            target=self._monitor, name="tfos-step-watchdog", daemon=True)
        self._thread.start()

    def arm(self) -> None:
        with self._lock:
            self._armed_at = time.monotonic()

    def beat(self) -> None:
        with self._lock:
            self._armed_at = None

    def stop(self) -> None:
        self._stop.set()

    def _monitor(self) -> None:
        poll = max(0.05, self.timeout_s / 4.0)
        while not self._stop.wait(poll):
            with self._lock:
                armed_at = self._armed_at
            if armed_at is None or self._fired:
                continue
            stalled = time.monotonic() - armed_at
            if stalled < self.timeout_s:
                continue
            self._fired = True
            reason = (f"train step stalled for {stalled:.0f}s "
                      f"(> step_timeout_s={self.timeout_s:.0f}) — "
                      "chip/slice wedged mid-run?")
            logger.critical("%s", reason)
            try:
                from tensorflowonspark_tpu import obs

                # the attributed record the driver's anomaly detector
                # (obs.anomaly.stall_events) later lifts off the
                # blackboard: pid + timings, not just a reason string
                obs.counter("watchdog_stalls_total").inc()
                obs.event("health.step_stall", reason=reason,
                          stalled_s=round(stalled, 1), pid=os.getpid(),
                          timeout_s=self.timeout_s)
                obs.flush()  # last chance before the hard exit below
            except Exception:
                pass
            try:
                if self._on_stall is not None:
                    self._on_stall(reason)
            finally:
                if self._exit:
                    os._exit(_STALL_EXIT_CODE)


def _probe_env_override() -> bool | None:
    """TFOS_HEALTH_PROBE parse shared by the bootstrap and serving
    policies: None when unset, else the forced verdict."""
    env = os.environ.get("TFOS_HEALTH_PROBE")
    if env is None:
        return None
    return env not in ("0", "", "false", "no")


def should_probe(cluster_meta: dict, chips: list) -> bool:
    """Decide whether this bootstrap should probe (see module docstring)."""
    override = _probe_env_override()
    if override is not None:
        return override
    configured = cluster_meta.get("health_probe")
    if configured is not None:
        return bool(configured)
    return bool(chips)


def should_probe_serving() -> bool:
    """Probe policy for the cluster-less serving path
    (``pipeline.single_node_env``): no cluster_meta and no chip claims
    exist there, so probe only on accelerator *evidence* —
    ``JAX_PLATFORMS`` naming a non-CPU backend first.  A plain CPU grid
    leaves it unset or ``cpu`` and pays nothing, matching the bootstrap
    default's zero healthy-path overhead.  ``TFOS_HEALTH_PROBE`` overrides
    both ways."""
    override = _probe_env_override()
    if override is not None:
        return override
    plat = os.environ.get("JAX_PLATFORMS", "")
    first = plat.split(",")[0].strip().lower()
    return bool(first) and first != "cpu"
