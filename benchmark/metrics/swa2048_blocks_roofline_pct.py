"""Kernels: the least time the chip could take for one step's
sliding-window attention at a window of 2,048 — the larger of operations
over peak FLOP/s and bytes over peak bytes/s, both from the configuration's
``work.py::window_attention_work`` (the two products of the scores inside
the window's band whatever the documents are, in the passes a step makes of
them, each operand moved once a pass) — over ``swa2048_blocks_device_ms``.
``run["notes"]`` gets which bound applies."""

from benchmark import afmoe_scopes
from benchmark.metrics import swa2048_blocks_device_ms


def read(run: dict):
    def need(work, config, cell):
        if not hasattr(work, "window_attention_work"):
            return None
        return work.window_attention_work(
            config, cell["traffic_values"]["batch_per_chip"] * cell["chips"]
            * config["seq_len"])

    return afmoe_scopes.roofline_pct(
        run, "swa2048_blocks_roofline_pct",
        swa2048_blocks_device_ms.read(run), need)
