"""Device: host time a traced step spent moving its batch to the device —
the runtime's transfer events and their host-side layout work
(``XlaLinearize``, ``H2D Dispatch``, ``TransferToDevice``), summed over the
runtime's threads, over the traced steps."""


def read(run: dict):
    trace = run["trainer"].get("trace")
    if not trace or not trace["steps"]:
        return None
    return 1e3 * trace["transfer_s"] / trace["steps"]
