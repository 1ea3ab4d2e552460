"""Small shared utilities.

Reference anchor: ``tensorflowonspark/util.py`` (``get_ip_address``,
``find_in_path``, ``write_executor_id``/``read_executor_id``).

Additions for the TPU rebuild:

- :func:`ensure_jax_platform` — applies ``TFOS_HOST_DEVICE_COUNT`` (a
  virtual multi-device CPU topology for tests and CPU-only CI) and the
  shard-invariant ``jax.random`` default before JAX initialises.  The
  platform itself is JAX's own ``JAX_PLATFORMS``.
- :func:`single_node_scratch_dir` — per-executor scratch directory used for
  the executor-id collision guard and chip-claim lock files.
"""

from __future__ import annotations

import errno
import logging
import os
import socket
import sys

logger = logging.getLogger(__name__)

# Environment knob: number of virtual host-platform devices to request.
HOST_DEVICE_COUNT_ENV = "TFOS_HOST_DEVICE_COUNT"

_jax_platform_applied = False


def ensure_jax_platform() -> None:
    """Apply ``TFOS_HOST_DEVICE_COUNT`` and the partitionable-threefry
    default to this process.

    Must be called before the first ``jax.devices()``/``jit`` in the process.
    Safe to call repeatedly.
    """
    global _jax_platform_applied
    if _jax_platform_applied:
        return
    # Shard-invariant randomness: the legacy threefry lowering is NOT
    # invariant under GSPMD partitioning — ``jax.random`` inside a jit
    # whose outputs carry shardings draws DIFFERENT values per mesh
    # layout, so the trainer's sharded init materialized different
    # parameters on a dp-only mesh than on an ep/tp one (the root cause
    # of the three BERT-MoE mesh-equivalence test failures).  The
    # partitionable implementation is the designed fix: same values for
    # the same key regardless of how the computation is sharded.
    # setdefault so an operator can still opt out.
    os.environ.setdefault("JAX_THREEFRY_PARTITIONABLE", "true")
    if "jax" in sys.modules:
        # jax read the env at import time; if someone imported it before
        # calling us, apply the flag through the live config instead
        import jax

        if os.environ["JAX_THREEFRY_PARTITIONABLE"].strip().lower() in (
                "1", "true", "yes"):
            jax.config.update("jax_threefry_partitionable", True)
    ndev = os.environ.get(HOST_DEVICE_COUNT_ENV)
    if ndev:
        flag = f"--xla_force_host_platform_device_count={int(ndev)}"
        existing = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in existing:
            os.environ["XLA_FLAGS"] = (existing + " " + flag).strip()
    _jax_platform_applied = True


def get_ip_address() -> str:
    """Best-effort routable IP of this host.

    Reference anchor: ``tensorflowonspark/util.py::get_ip_address`` (the UDP
    connect trick — no packet is actually sent).
    """
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect(("8.8.8.8", 80))
        return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"
    finally:
        s.close()


def find_in_path(path: str, file_name: str) -> str | None:
    """Find ``file_name`` in the ``os.pathsep``-separated ``path``.

    Reference anchor: ``tensorflowonspark/util.py::find_in_path``.
    """
    for p in path.split(os.pathsep):
        candidate = os.path.join(p, file_name)
        if os.path.exists(candidate) and os.path.isfile(candidate):
            return candidate
    return None


def single_node_scratch_dir(app_id: str) -> str:
    """Per-application scratch directory on this host (created on demand)."""
    d = os.path.join(
        os.environ.get("TFOS_SCRATCH_ROOT", "/tmp"), f"tfos_tpu_{app_id}"
    )
    os.makedirs(d, exist_ok=True)
    return d


def _executor_id_file(dir_path: str | None = None, name: str = "executor_id") -> str:
    return os.path.join(dir_path or os.getcwd(), name)


def write_executor_id(
    num: int, dir_path: str | None = None, name: str = "executor_id"
) -> None:
    """Record this executor's cluster node id in its working directory.

    Reference anchor: ``tensorflowonspark/util.py::write_executor_id``.  Used
    as a collision guard: if Spark schedules two cluster-bootstrap tasks onto
    the same executor, the second one sees an existing id file and fails fast
    instead of silently forming a malformed cluster.  ``name`` lets callers
    scope the guard per cluster instance (e.g. ``executor_id_<cluster_id>``)
    so sequential clusters on one SparkContext don't trip over stale files.
    """
    with open(_executor_id_file(dir_path, name), "w", encoding="utf-8") as f:
        f.write(str(num))


def read_executor_id(
    dir_path: str | None = None, name: str = "executor_id"
) -> int | None:
    """Read the executor id written by :func:`write_executor_id`, if any."""
    try:
        with open(_executor_id_file(dir_path, name), encoding="utf-8") as f:
            return int(f.read())
    except OSError as e:
        if e.errno in (errno.ENOENT,):
            return None
        raise


def find_free_port(host: str = "") -> tuple[str, int]:
    """Bind an ephemeral TCP port and return ``(hostname, port)``.

    The socket is closed before returning; the reservation protocol only needs
    a port number that was recently free (same contract as the reference's
    port grab in ``TFSparkNode.py::_mapfn``).
    """
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind((host, 0))
    port = s.getsockname()[1]
    s.close()
    return (host or get_ip_address(), port)
