"""Wide&deep's whole step under both executions of the default table update,
on the shapes that fix ``models/widedeep.py::ROWS_PER_ID_CROSSOVER``.

The default (``table_update="dense"``) AdaGrad runs as a pass over the whole
table or on the looked-up rows alone, chosen from static shapes by
``widedeep.update_touches_rows``.  The benchmark has a cell on the rows side
only (``widedeep_spark_fed``, 635 table rows an id), so this tool is what
measures the other side and the threshold: for each ``buckets x batch`` shape
it forces each execution in turn (by setting the crossover for that run, as
``tests/test_models.py`` does), times ``--repeats`` runs of ``--steps`` steps
on one staged Zipf batch, and prints the table beside the constant, the two
fitted costs and the ratio at which they meet.

On the chip, from the root of the repo (about 3 minutes)::

    python tools/table_update_crossover.py

One device only: nothing here times vocab-sharded tables (``tp``).  A CPU
run (``--shapes 50x8,200x8 --steps 2``) proves the tool, not a number.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: buckets a feature x batch: the four shapes of the table in ``widedeep.py``
SHAPES = "650000x1024,650000x4096,100000x1024,100000x4096"
FORCE = {"rows": 0, "full": 10 ** 9}  # a crossover that takes that side


def zipf_batch(config, batch_size: int, seed: int) -> dict:
    """Ids by a Zipf law (s = 1.05) over a feature's buckets, scattered over
    the table by a multiplicative hash: the benchmark's traffic, cheaply."""
    import numpy as np

    from tensorflowonspark_tpu.models import widedeep

    rng = np.random.RandomState(seed)
    p = 1.0 / np.arange(1, config.hash_buckets + 1) ** 1.05
    ranks = np.searchsorted(np.cumsum(p / p.sum()),
                            rng.rand(batch_size, widedeep.NUM_CAT))
    ranks = ranks.clip(0, config.hash_buckets - 1).astype(np.int64)
    batch = widedeep.example_batch(config, batch_size=batch_size, seed=seed)
    batch["cat"] = (ranks * 2654435761 % config.hash_buckets).astype(np.int32)
    return batch


def time_step(buckets: int, batch_size: int, path: str, steps: int,
              repeats: int) -> float:
    """Median ms a step of ``repeats`` runs of ``steps`` steps."""
    import statistics

    import jax

    from tensorflowonspark_tpu.models import widedeep
    from tensorflowonspark_tpu.trainer import Trainer

    config = widedeep.Config(hash_buckets=buckets)
    crossover = widedeep.ROWS_PER_ID_CROSSOVER
    # read when the step traces, and by the step's counters at every call
    widedeep.ROWS_PER_ID_CROSSOVER = FORCE[path]
    try:
        trainer = Trainer("wide_deep", config=config,
                          devices=jax.devices()[:1])
        staged = trainer.shard(zipf_batch(config, batch_size, seed=7))
        state = trainer.state
        for _ in range(3):
            state, loss = trainer.train_step(state, staged)
        jax.block_until_ready(loss)
        times = []
        for _ in range(repeats):
            start = time.perf_counter()
            for _ in range(steps):
                state, loss = trainer.train_step(state, staged)
            jax.block_until_ready(loss)
            times.append((time.perf_counter() - start) / steps * 1e3)
    finally:
        widedeep.ROWS_PER_ID_CROSSOVER = crossover
    del trainer, state, staged, loss
    gc.collect()  # the next shape's tables need the room
    return statistics.median(times)


def fit(rows: list) -> dict:
    """``full = a * table rows + b * ids`` and ``rows = c * ids`` by least
    squares over the measured shapes, in ns; they meet at ``(c - b) / a``
    table rows an id."""
    import numpy as np

    from tensorflowonspark_tpu.models import widedeep

    table = np.array([r["buckets"] * widedeep.NUM_CAT for r in rows], float)
    ids = np.array([r["batch"] * widedeep.NUM_CAT for r in rows], float)
    (a, b), *_ = np.linalg.lstsq(
        np.stack([table, ids], axis=1),
        np.array([r["full_ms"] for r in rows]) * 1e6, rcond=None)
    c = float(np.sum(ids * np.array([r["rows_ms"] for r in rows]) * 1e6)
              / np.sum(ids * ids))
    return {"full_ns_a_table_row": float(a), "full_ns_an_id": float(b),
            "rows_ns_an_id": c, "rows_per_id_crossover": (c - b) / a}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--shapes", default=SHAPES,
                   help="comma-separated <buckets a feature>x<batch>")
    p.add_argument("--steps", type=int, default=40)
    p.add_argument("--repeats", type=int, default=3)
    args = p.parse_args(argv)

    import jax

    from tensorflowonspark_tpu.models import widedeep

    device = jax.devices()[0]
    print(f"device: {device.platform} {device.device_kind}; the rule today: "
          f"rows pass from {widedeep.ROWS_PER_ID_CROSSOVER} table rows an id",
          flush=True)
    rows = []
    for shape in args.shapes.split(","):
        buckets, batch_size = (int(n) for n in shape.split("x"))
        row = {"buckets": buckets, "batch": batch_size,
               "rows_per_id": buckets / batch_size}
        for path in FORCE:
            row[f"{path}_ms"] = time_step(buckets, batch_size, path,
                                          args.steps, args.repeats)
        row["rule_takes"] = "rows" if widedeep.update_touches_rows(
            buckets * widedeep.NUM_CAT, batch_size * widedeep.NUM_CAT
        ) else "full"
        print(json.dumps(row), flush=True)
        rows.append(row)
    if len(rows) >= 3:
        print(json.dumps(fit(rows)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
