"""The ``resnet50`` configuration on the program's side: how the benchmark
builds the system under test for it, hands it the seeded weights, and reads
back what the output check compares.  Everything the reference must not
touch lives here; the reference lives next door and imports none of this.
"""

from __future__ import annotations

import re

BATCH_KEYS = ("image", "label")


def build(config: dict, ctx=None):
    """The Trainer a user's ``map_fun`` builds for this model."""
    from tensorflowonspark_tpu.models import resnet
    from tensorflowonspark_tpu.trainer import Trainer

    model_config = resnet.Config(
        stage_sizes=tuple(config["stage_sizes"]), width=config["width"],
        num_classes=config["num_classes"], image_size=config["image_size"],
        groups=config["groups"], dtype=config["dtype"], norm=config["norm"])
    opt = config["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError("the resnet50 program runs Trainer's default AdamW")
    return Trainer(config["program_model"], config=model_config,
                   learning_rate=opt["learning_rate"],
                   error_sink=getattr(ctx, "report_error", None))


def program_path(config: dict, name: str) -> tuple:
    """The flax parameter path of the reference's leaf ``name``."""
    if name.startswith("stem/"):
        return {"stem/conv": ("Conv_0", "kernel"),
                "stem/norm/scale": ("GroupNorm_0", "scale"),
                "stem/norm/bias": ("GroupNorm_0", "bias")}[name]
    if name.startswith("head/"):
        return ("Dense_0", name.split("/")[1])
    m = re.fullmatch(r"s(\d+)b(\d+)/(conv|norm|proj|projnorm)(\d?)(/\w+)?",
                     name)
    stage, block, kind, idx, leaf = m.groups()
    n = sum(config["stage_sizes"][:int(stage)]) + int(block)
    slot = 3 if kind.startswith("proj") else int(idx) - 1
    if kind in ("conv", "proj"):
        return (f"Bottleneck_{n}", f"Conv_{slot}", "kernel")
    return (f"Bottleneck_{n}", f"GroupNorm_{slot}", leaf[1:])


def _by_name(config: dict, tree, names) -> dict:
    out = {}
    for name in names:
        node = tree
        for key in program_path(config, name):
            node = node[key]
        out[name] = node
    return out


def load_weights(trainer, config: dict, reference, seed: int) -> list:
    """Put the benchmark's seeded weights where the Trainer keeps its own,
    in the layout the compiled step expects (what ``Trainer.restore`` does
    with a checkpoint).  Returns the leaves' names."""
    import jax

    from tensorflowonspark_tpu.parallel.train import TrainState

    weights = reference.make_weights(config, seed)
    names = list(weights)

    def rebuild(node, prefix=()):
        if isinstance(node, dict):
            return {k: rebuild(v, prefix + (k,)) for k, v in node.items()}
        return by_path.pop(prefix)

    by_path = {program_path(config, n): w for n, w in weights.items()}
    params = rebuild(trainer.state.params)
    if by_path:
        raise ValueError(f"weights the program has no place for: "
                         f"{sorted(by_path)}")
    params = jax.device_put(params, trainer.param_shardings)
    state = trainer.state
    trainer.state = TrainState(params, state.opt_state, state.step,
                               state.collections)
    return names


def parameters(trainer, config: dict, names) -> dict:
    """The program's current parameters under the reference's names."""
    return _by_name(config, trainer.state.params, names)


def first_gradient_norms(trainer, config: dict, names) -> dict:
    """Per-leaf norm of the first gradient as the optimizer got it, worked
    out from its state after one step: AdamW's first moment is then
    ``(1 - b1)`` times that gradient."""
    import jax
    import jax.numpy as jnp

    stack = [trainer.state.opt_state]
    while stack:
        node = stack.pop()
        if hasattr(node, "mu") and hasattr(node, "nu"):
            scale = 1.0 / (1.0 - config["optimizer"]["b1"])
            norms = jax.jit(lambda tree: {
                k: scale * jnp.sqrt(jnp.sum(jnp.square(v)))
                for k, v in tree.items()})(_by_name(config, node.mu, names))
            return {k: float(v) for k, v in jax.device_get(norms).items()}
        if isinstance(node, (tuple, list)):
            stack.extend(node)
    raise RuntimeError("no Adam state in the Trainer's optimizer state")


def host_batch(columns: dict) -> dict:
    """Columns as the feed delivers them -> the step's batch (dtypes)."""
    import numpy as np

    return {"image": np.asarray(columns["image"], np.float32),
            "label": np.asarray(columns["label"], np.int32)}


def tfrecord_parse_fn(config: dict):
    """The example's record parser, with the record's ``id`` kept."""
    import numpy as np

    from tensorflowonspark_tpu import tfrecord

    side = config["image_size"]

    def parse(payload: bytes):
        ex = tfrecord.decode_example(payload)
        img = np.frombuffer(ex["image"][1][0], np.uint8)
        return {
            "image": img.reshape(side, side, 3).astype(np.float32) / 255.0,
            "label": np.int32(ex["label"][1][0]),
            "id": np.int64(ex["id"][1][0]),
        }

    return parse
