"""Feed planes: bytes of the batches staged for steps that completed in the
window, over the window's seconds (a count of work, in MB/s)."""


def read(run: dict):
    window = run["trainer"]["window"]
    return window["bytes"] / window["seconds"] / 1e6
