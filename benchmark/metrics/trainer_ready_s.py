"""Trainer and compile cache: ``import jax`` in the ``map_fun`` to the end of
the first training step (backend start, ``Trainer()``, seeded weights, the
feed's first batch, compile or cache load, one step)."""


def read(run: dict):
    return run["trainer"]["trainer_ready_s"]
