"""Operations and bytes one ResNet-50 training step needs, from shapes.

A lower bound on both, so a roofline share built on it cannot pass 100%:
every convolution and the classifier counted once forward and twice
backward (gradient to the input and to the weights; the first convolution
has no input gradient to make), nothing recomputed, no normalisation,
activation or pooling arithmetic; of the bytes only what no schedule can
avoid — the input batch read once, and the optimizer's pass over the
parameters (read parameter, gradient, both moments; write parameter and
both moments), all float32.
"""

from __future__ import annotations


def conv_layers(config: dict):
    """Yield ``(kernel, c_in, c_out, out_side)`` of every convolution of
    the v1.5 bottleneck network, in forward order."""
    side = config["image_size"] // 2          # 7x7 stride-2 stem
    yield 7, 3, config["width"], side
    side //= 2                                # 3x3 stride-2 max pool
    c_in = config["width"]
    for stage, blocks in enumerate(config["stage_sizes"]):
        filters = config["width"] * 2 ** stage
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            yield 1, c_in, filters, side                     # 1x1 reduce
            out = side // stride
            yield 3, filters, filters, out                   # 3x3 (strided)
            yield 1, filters, 4 * filters, out               # 1x1 expand
            if c_in != 4 * filters or stride != 1:
                yield 1, c_in, 4 * filters, out              # projection
            c_in, side = 4 * filters, out


def forward_macs_per_image(config: dict) -> int:
    macs = sum(k * k * c_in * c_out * side * side
               for k, c_in, c_out, side in conv_layers(config))
    last = config["width"] * 2 ** (len(config["stage_sizes"]) - 1) * 4
    return macs + last * config["num_classes"]


def train_flops_per_image(config: dict) -> int:
    forward = 2 * forward_macs_per_image(config)
    stem = 2 * 7 * 7 * 3 * config["width"] * (config["image_size"] // 2) ** 2
    return 3 * forward - stem


def step_work(config: dict, batch: int) -> dict:
    side = config["image_size"]
    params = config["parameters"]
    return {
        "flops": batch * train_flops_per_image(config),
        "bytes": batch * side * side * 3 * 4 + 7 * 4 * params,
        "examples": batch,
    }
