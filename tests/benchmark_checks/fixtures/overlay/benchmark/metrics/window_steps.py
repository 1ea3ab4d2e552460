"""Fixture: a per-layer metric added as a file of its own."""


def read(run: dict):
    return run["trainer"]["window"]["steps"]
