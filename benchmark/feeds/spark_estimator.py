"""Feed plane ``spark_estimator``: ``InputMode.SPARK`` through
``TFEstimator.fit`` on a DataFrame, as ``examples/criteo/criteo_pipeline.py``
does: Spark partitions go through the executor's queue and shared memory to
``DataFeed.next_batch``, which stages them with ``Trainer.shard``.

Rows are accounted for: rows the driver fed = rows in full batches the
trainer took + rows in short tail batches (partition ends) the loop dropped
+ rows drained when the feed was ended + rows of epochs the feeders
discarded after that.
"""

from __future__ import annotations

import time

from benchmark.feeds import Item


def drive(plan: dict, sc, map_fun) -> dict:
    """The driver program's part: a DataFrame of the seeded rows, fitted."""
    import numpy as np

    from tensorflowonspark_tpu import TFCluster
    from tensorflowonspark_tpu.pipeline import TFEstimator
    from tensorflowonspark_tpu.sparkapi.sql import LocalSparkSession

    traffic = plan["traffic"]
    marks = {"t_dataframe": time.time()}
    with np.load(plan["data"]["npz"]) as data:
        dense, cat, label = data["dense"], data["cat"], data["label"]
    rows = list(zip(dense.tolist(), cat.tolist(), label.tolist(),
                    range(len(label))))
    del dense, cat, label
    df = LocalSparkSession(sc).createDataFrame(
        rows, ["dense", "cat", "label", "id"]).repartition(
            traffic["partitions"])
    del rows
    estimator = (TFEstimator(map_fun, tf_args=plan)
                 .setClusterSize(1)
                 .setBatchSize(plan["batch"])
                 .setEpochs(traffic["max_epochs"])
                 .setGraceSecs(plan["timeout_s"]))
    # fit() feeds every epoch and only then shuts the cluster down; the
    # benchmark's span round that inner call says when tear-down began
    shutdown = TFCluster.TFCluster.shutdown

    def timed_shutdown(cluster, *args, **kwargs):
        marks["t_shutdown_called"] = time.time()
        return shutdown(cluster, *args, **kwargs)

    TFCluster.TFCluster.shutdown = timed_shutdown
    marks["t_cluster_run"] = time.time()
    try:
        estimator.fit(df)
    finally:
        TFCluster.TFCluster.shutdown = shutdown
    marks["t_shutdown_returned"] = time.time()
    return marks


class Feed:
    def __init__(self, plan, ctx, program, trainer, batch):
        import jax

        traffic = plan["traffic"]
        self._ctx, self._batch = ctx, batch
        self._feed = ctx.get_data_feed(
            train_mode=True, input_mapping=["dense", "cat", "label", "id"],
            prefetch=traffic["prefetch"])
        self.rows_taken = self.rows_dropped = self.short_batches = 0
        self._ids = []
        self._rows = plan["data"]["records"]

        def stage(columns):
            # short tail batches (partition ends) stay on the host: the
            # loop drops them, as the example's does
            ids = columns.pop("id")
            self._ids.append(ids)
            if len(ids) != batch:
                return Item(None, ids, 0)
            host = program.host_batch(columns)
            nbytes = sum(int(v.nbytes) for v in host.values())
            with jax.profiler.TraceAnnotation("stage_batch"):
                return Item(trainer.shard(host), ids, nbytes)

        self._stage = stage

    def next(self):
        while not self._feed.should_stop():
            item = self._feed.next_batch(self._batch, device_put=self._stage)
            if not isinstance(item, Item):      # an empty batch at a marker
                continue
            if item.batch is None:
                self.rows_dropped += item.rows
                self.short_batches += 1
                continue
            self.rows_taken += item.rows
            return item
        return None

    def end(self) -> dict:
        """End the feed as the reference's ``DataFeed.terminate`` does: tell
        the feeders (node state ``terminating``) and drain the queue."""
        from benchmark import check

        self._ctx.mgr.set("state", "terminating")
        self._feed.terminate()
        # one feeder sends the partitions in order, pass after pass, so
        # every pass must bring the same rows in the same order
        out = check.epoch_accounting(self._ids, self._rows, same_order=True)
        out.update(rows_taken=self.rows_taken,
                   rows_dropped_short=self.rows_dropped,
                   short_batches=self.short_batches)
        return out


def open_feed(plan, ctx, program, trainer, batch) -> Feed:
    return Feed(plan, ctx, program, trainer, batch)
