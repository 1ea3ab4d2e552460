"""Feed plane ``tfrecord_readers``: ``InputMode.TENSORFLOW``.  The trainer
reads its shard of the TFRecord files with the program's readers, as
``examples/imagenet/resnet_spark.py`` does; the driver only starts the
cluster and waits for it.
"""

from __future__ import annotations

import time

from benchmark.feeds import Item


def drive(plan: dict, sc, map_fun) -> dict:
    """The driver program's part: what a user's job calls, in order."""
    from tensorflowonspark_tpu import TFCluster

    marks = {"t_cluster_run": time.time()}
    cluster = TFCluster.run(
        sc, map_fun, plan, num_executors=1,
        input_mode=TFCluster.InputMode.TENSORFLOW,
        num_chips_per_executor=plan["claim_chips"], master_node="chief")
    marks["t_shutdown_called"] = time.time()
    cluster.shutdown(timeout=plan["timeout_s"])
    marks["t_shutdown_returned"] = time.time()
    marks["claimed_chips"] = cluster.cluster_info[0].get("chips")
    return marks


class Feed:
    def __init__(self, plan, ctx, program, trainer, batch):
        import jax

        from tensorflowonspark_tpu import readers

        traffic = plan["traffic"]
        files = readers.shard_files(plan["data"]["glob"], ctx.executor_id,
                                    ctx.num_workers)
        if not files:
            raise RuntimeError(f"no record files at {plan['data']['glob']}")

        self._ids = []

        def stage(columns):
            ids = columns.pop("id")
            self._ids.append(ids)
            host = program.host_batch(columns)
            nbytes = sum(int(v.nbytes) for v in host.values())
            with jax.profiler.TraceAnnotation("stage_batch"):
                return Item(trainer.shard(host), ids, nbytes)

        self._gen = readers.tfrecord_batches(
            files, batch,
            parse_fn=program.tfrecord_parse_fn(plan["config"]),
            num_epochs=traffic["max_epochs"], readers=traffic["readers"],
            shuffle_buffer=traffic["shuffle_buffer"], shuffle_files=True,
            seed=plan["seed"] % (2 ** 32), drop_remainder=True,
            prefetch=traffic["prefetch"], device_put=stage)
        self.files = len(files)
        self._records = plan["data"]["records"]

    def next(self):
        return next(self._gen, None)

    def end(self) -> dict:
        """Abandon the iterator: the pump and the reader pool stop."""
        from benchmark import check

        self._gen.close()
        # batches staged ahead but never handed out are in the log too: the
        # log is of rows the readers delivered, in delivery order
        out = check.epoch_accounting(
            self._ids, self._records, same_order=False)
        out["files"] = self.files
        return out


def open_feed(plan, ctx, program, trainer, batch) -> Feed:
    return Feed(plan, ctx, program, trainer, batch)
