"""Trainer: median of ``trainer.step()`` from the call to the loss on the
host, over the window's steps."""

from benchmark import stats


def read(run: dict):
    steps = run["trainer"]["window"]["step_s"]
    return 1e3 * stats.median(steps) if steps else None
