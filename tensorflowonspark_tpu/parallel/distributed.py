"""Multi-host JAX runtime initialisation, seeded by the rendezvous barrier.

Reference anchor: the reference wires ``TF_CONFIG`` + ``tf.train.Server``
(``TFSparkNode.py::_mapfn``, ``TFNode.py::start_cluster_server``) so TF's
gRPC runtime can form a cluster.  The TPU equivalent is
``jax.distributed.initialize(coordinator_address, num_processes,
process_id)``: afterwards ``jax.devices()`` spans every host's chips and XLA
collectives ride ICI/DCN.

The coordinator is the node with ``executor_id == 0`` — its rendezvous
``host:port`` (a port reserved during bootstrap) doubles as the coordination
service address, so no extra configuration is needed beyond the cluster_info
every node already holds.
"""

from __future__ import annotations

import logging
import os

logger = logging.getLogger(__name__)

# Set TFOS_JAX_DISTRIBUTED=0 to force single-process JAX even in a multi-node
# cluster (each node then sees only its own chips — the reference's
# "between-graph, no collectives" shape). Default: initialise when the
# cluster has more than one node and real accelerators are present.
DISTRIBUTED_ENV = "TFOS_JAX_DISTRIBUTED"

_initialized = False


def coordinator_address(cluster_info) -> str:
    # the LOWEST surviving executor id, not literally 0: after an elastic
    # regroup executor 0 may be among the lost (elastic.py picks the same
    # node as the new generation's coordinator)
    node0 = min(cluster_info, key=lambda m: m["executor_id"])
    return f"{node0['host']}:{node0['port']}"


def maybe_initialize(ctx) -> bool:
    """Initialise ``jax.distributed`` for this node if appropriate.

    Returns True when the distributed runtime was (already) initialised.
    No-op for single-node clusters, when ``TFOS_JAX_DISTRIBUTED=0``, or when
    no accelerator chips are present (CPU test topology — cross-process CPU
    collectives are not part of the test contract; multi-chip behavior is
    validated on a virtual in-process mesh instead, ``SURVEY.md §4``).
    """
    global _initialized
    if _initialized:
        return True
    flag = os.environ.get(DISTRIBUTED_ENV, "auto")
    if flag == "0":
        return False
    num_nodes = ctx.num_workers
    if num_nodes <= 1:
        return False
    from tensorflowonspark_tpu import chip_info

    if flag != "1" and chip_info.get_num_host_chips() == 0:
        logger.info(
            "multi-node cluster on chip-less hosts: skipping "
            "jax.distributed.initialize (set %s=1 to force)", DISTRIBUTED_ENV,
        )
        return False

    from tensorflowonspark_tpu import util

    util.ensure_jax_platform()
    import jax

    if chip_info.get_num_host_chips() == 0:
        # Forced multi-process on chip-less hosts (tests, CPU clusters): the
        # CPU backend needs an explicit cross-process collectives impl before
        # backend init, or every process sees only its own local devices.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")

    addr = coordinator_address(ctx.cluster_info)
    timeout_s = int(os.environ.get("TFOS_JAX_DISTRIBUTED_TIMEOUT", "300"))
    # process ids must be contiguous 0..n-1: after an elastic regroup the
    # surviving executor ids have holes (e.g. 0 and 2 of an original 3),
    # so each node's process id is its POSITION among the membership's
    # sorted executor ids (identical to executor_id for a fresh cluster)
    ids = sorted(m["executor_id"] for m in ctx.cluster_info)
    process_id = ids.index(ctx.executor_id)
    logger.info(
        "jax.distributed.initialize(coordinator=%s, num_processes=%d, "
        "process_id=%d)", addr, num_nodes, process_id,
    )
    jax.distributed.initialize(
        coordinator_address=addr,
        num_processes=num_nodes,
        process_id=process_id,
        initialization_timeout=timeout_s,
    )
    _initialized = True
    return True


def maybe_shutdown() -> bool:
    """Tear down the distributed runtime if this process initialised it.

    The elastic rejoin path (``elastic.ElasticWorker.rejoin``) calls this
    before re-entering the rendezvous: a runtime still pinned to dead
    peers would wedge the first collective of the new generation.  No-op
    (returns False) when the runtime was never formed — the CPU test
    substrate and single-node clusters.
    """
    global _initialized
    if not _initialized:
        return False
    import jax

    try:
        jax.distributed.shutdown()
    except Exception as e:  # best-effort: the old world may be half-dead
        logger.warning("jax.distributed.shutdown failed: %s", e)
    _initialized = False
    return True
