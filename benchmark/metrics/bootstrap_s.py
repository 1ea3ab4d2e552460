"""Cluster and node runtime: ``TFCluster.run`` (or ``TFEstimator.fit``) called
in the driver to the first line of the ``map_fun`` in the trainer process —
executor task, chip claim, rendezvous, health-probe child, trainer start."""


def read(run: dict):
    return run["trainer"]["t_map_fun"] - run["driver"]["t_cluster_run"]
