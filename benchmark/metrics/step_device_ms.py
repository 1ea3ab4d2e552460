"""Trainer: device busy time a traced step — the union of the intervals in
which an operation ran on the device over the traced steps, averaged over
the chips, divided by the steps dispatched in them."""


def read(run: dict):
    trace = run["trainer"].get("trace")
    if not trace or not trace["steps"]:
        return None
    return 1e3 * trace["busy_s"] / trace["steps"]
