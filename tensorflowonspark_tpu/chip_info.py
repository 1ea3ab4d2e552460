"""TPU chip discovery and per-executor chip claiming.

Reference anchor: ``tensorflowonspark/gpu_info.py::get_gpus`` — the reference
parses ``nvidia-smi`` for free GPUs and retries with random backoff when
multiple executors on one host race for the same device, then exports
``CUDA_VISIBLE_DEVICES``.

TPU rebuild: chips are not "busy/free" observable via a CLI — the TPU runtime
grabs every chip the process can see at first JAX init, for the lifetime of
the process.  So instead of *probing*, executors must *partition* the host's
chips ahead of time.  We do that with atomic lock files in a per-host claim
directory (``O_CREAT|O_EXCL`` — the same idea as the reference's collision
guard, but race-free rather than retry-until-quiet), then pin visibility with
``TPU_VISIBLE_CHIPS``/``TPU_CHIPS_PER_PROCESS_BOUNDS`` before JAX starts.

The retry/backoff loop (``MAX_RETRIES``) is kept for the case where a
just-killed executor's stale claim file still exists and is being reaped.
"""

from __future__ import annotations

import glob
import logging
import os
import random
import time

logger = logging.getLogger(__name__)

MAX_RETRIES = 3  # parity: tensorflowonspark/gpu_info.py::MAX_RETRIES
_CLAIM_STALE_SECS = 600.0


def get_num_host_chips() -> int:
    """Number of TPU chips attached to this host.

    ``TFOS_NUM_CHIPS`` overrides (tests, CPU hosts).  Otherwise the chips
    are counted from their device nodes: a v5e host exposes one VFIO group
    per chip as ``/dev/vfio/<n>`` (beside the ``/dev/vfio/vfio`` container
    node); older TPU VMs expose ``/dev/accel<n>``.  The ``TPU_*``
    environment is NOT consulted: a machine holding one chip of a four-chip
    host still carries ``TPU_ACCELERATOR_TYPE=v5litepod-4`` and
    ``TPU_CHIPS_PER_HOST_BOUNDS=2,2,1``.
    """
    override = os.environ.get("TFOS_NUM_CHIPS")
    if override:
        return int(override)
    vfio = [p for p in glob.glob("/dev/vfio/*")
            if os.path.basename(p).isdigit()]
    return len(vfio) or len(glob.glob("/dev/accel*"))


def _claim_dir(app_id: str) -> str:
    from tensorflowonspark_tpu import util

    d = os.path.join(util.single_node_scratch_dir(app_id), "chip_claims")
    os.makedirs(d, exist_ok=True)
    return d


def claim_chips(num_chips: int, app_id: str, worker_tag: str) -> list[int]:
    """Atomically claim ``num_chips`` of this host's chips for one executor.

    Returns the claimed chip indices.  Raises ``RuntimeError`` when the host
    does not have enough unclaimed chips after ``MAX_RETRIES`` passes (stale
    claims older than ``_CLAIM_STALE_SECS`` are reaped between passes).
    """
    total = get_num_host_chips()
    if total == 0:
        logger.info("no TPU chips visible on this host; nothing to claim")
        return []
    if num_chips > total:
        raise RuntimeError(
            f"requested {num_chips} chips but host has only {total}"
        )
    d = _claim_dir(app_id)
    for attempt in range(MAX_RETRIES + 1):
        claimed: list[int] = []
        for chip in range(total):
            if len(claimed) == num_chips:
                break
            path = os.path.join(d, f"chip_{chip}.lock")
            try:
                fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
            except FileExistsError:
                continue
            with os.fdopen(fd, "w", encoding="utf-8") as f:
                f.write(f"{worker_tag}\n{os.getpid()}")
            claimed.append(chip)
        if len(claimed) == num_chips:
            logger.info("claimed chips %s for %s", claimed, worker_tag)
            _release_at_exit(claimed, app_id)
            return claimed
        release_chips(claimed, app_id)  # partial claim — roll back and retry
        _reap_stale_claims(d)
        if attempt < MAX_RETRIES:
            time.sleep(random.uniform(0.1, 1.0) * (attempt + 1))
    raise RuntimeError(
        f"could not claim {num_chips} free chips on this host for {worker_tag}"
    )


def _release_at_exit(chips: list[int], app_id: str) -> None:
    """Release claims when this process exits normally.

    A SIGKILLed process can't run this — its claims are reaped later by
    :func:`_reap_stale_claims` once the recorded pid is dead.
    """
    import atexit

    atexit.register(release_chips, list(chips), app_id)


def release_chips(chips: list[int], app_id: str) -> None:
    """Release claims owned by *this process*.

    Ownership is verified against the pid recorded in the lock file so a
    lingering process's (atexit) release cannot destroy a successor's live
    claim on the same chip index.
    """
    d = _claim_dir(app_id)
    for chip in chips:
        path = os.path.join(d, f"chip_{chip}.lock")
        try:
            with open(path, encoding="utf-8") as f:
                lines = f.read().splitlines()
            owner_pid = int(lines[1]) if len(lines) > 1 else None
            if owner_pid is not None and owner_pid != os.getpid():
                continue
            os.unlink(path)
        except (OSError, ValueError):
            pass


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:
        return True  # exists, owned by someone else


def _reap_stale_claims(d: str) -> None:
    """Remove claims whose owning process is dead.

    A claim is only reaped when the pid recorded in the lock file no longer
    exists — mtime alone would reap a *live* executor that has simply been
    training for a long time.  Claims without a readable pid fall back to a
    (long) mtime threshold.
    """
    now = time.time()
    for path in glob.glob(os.path.join(d, "chip_*.lock")):
        try:
            with open(path, encoding="utf-8") as f:
                lines = f.read().splitlines()
            pid = int(lines[1]) if len(lines) > 1 else None
            if pid is not None:
                stale = not _pid_alive(pid)
            else:
                stale = now - os.path.getmtime(path) > _CLAIM_STALE_SECS
            if stale:
                os.unlink(path)
                logger.warning("reaped stale chip claim %s", path)
        except (OSError, ValueError):
            pass


#: ``TPU_CHIPS_PER_PROCESS_BOUNDS`` by claim size: the claim's shape on the
#: host's chip grid, not a flat count — a v5e host is 2x2, so four chips are
#: ``2,2,1``.  All three came up on a v5e host (PR 21).  The flat ``4,1,1``
#: did too, but only because that host's ``TPU_CHIPS_PER_HOST_BOUNDS`` told
#: the runtime the grid anyway.
_PROCESS_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}


def set_visibility_env(chips: list[int]) -> None:
    """Pin the TPU runtime to ``chips`` before JAX initialises.

    The TPU analogue of the reference exporting ``CUDA_VISIBLE_DEVICES``
    (``gpu_info.py::get_gpus`` caller side).  Must run before the first JAX
    device query in the process.

    Also pins ``JAX_PLATFORMS=tpu`` for this process and the children that
    inherit the claim (health probe, trainer): with chips claimed, a TPU
    that fails to initialise must raise, not leave JAX quietly on the CPU.
    """
    if not chips:
        return
    if len(chips) not in _PROCESS_BOUNDS:
        raise ValueError(
            f"cannot pin {len(chips)} chips to one process: a claim must "
            f"be a rectangle of the host's chip grid "
            f"({sorted(_PROCESS_BOUNDS)} chips)")
    os.environ["TPU_VISIBLE_CHIPS"] = ",".join(str(c) for c in chips)
    os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] = _PROCESS_BOUNDS[len(chips)]
    os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")
    # several executors of one host each load libtpu for their own chips
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    os.environ["JAX_PLATFORMS"] = "tpu"


def verify_claim(chips: list[int]) -> None:
    """Raise unless JAX in this process came up on exactly ``chips``.

    Called by the node runtime in the process that owns the claim, before
    the user's ``map_fun``: a claim of TPU chips that ends on another
    platform, or on a different number of local devices, is a named error
    at the driver, never a quiet run on the CPU.  No-op for a node that
    claimed nothing (CPU hosts, tests).
    """
    if not chips:
        return
    import jax

    devices = jax.local_devices()
    if devices[0].platform != "tpu" or len(devices) != len(chips):
        raise RuntimeError(
            f"claimed TPU chips {chips} but JAX came up on {len(devices)} "
            f"{devices[0].platform} device(s) ({devices[0].device_kind}); "
            "refusing to train on anything but the claimed chips")
