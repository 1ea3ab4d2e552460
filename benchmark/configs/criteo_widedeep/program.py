"""The ``criteo_widedeep`` configuration on the program's side: how the
benchmark builds the system under test for it, hands it the seeded weights,
and reads back what the output check compares.  The reference lives next
door and imports none of this.
"""

from __future__ import annotations

import functools

NUM_CAT = 26
BATCH_KEYS = ("dense", "cat", "label")


def build(config: dict, ctx=None):
    """The Trainer the example's ``train_fun`` builds for this model."""
    from tensorflowonspark_tpu.models import widedeep
    from tensorflowonspark_tpu.trainer import Trainer

    if config["table_update"] != widedeep.Config().table_update:
        raise ValueError("table_update is left at the program's default")
    model_config = widedeep.Config(
        hash_buckets=config["hash_buckets"], embed_dim=config["embed_dim"],
        hidden=tuple(config["hidden"]), dtype=config["dtype"],
        table_dtype=config["table_dtype"],
        table_lr=config["table_optimizer"]["learning_rate"],
        table_update=config["table_update"])
    return Trainer(config["program_model"], config=model_config,
                   learning_rate=config["optimizer"]["learning_rate"],
                   error_sink=getattr(ctx, "report_error", None))


def _mlp_path(name: str) -> tuple:
    layer, leaf = name.split("/")
    return (f"Dense_{int(layer[3:])}", leaf)


@functools.lru_cache(maxsize=None)
def _take_fn(buckets: int):
    """One compiled slice for all features: the feature's number is traced."""
    import jax

    return jax.jit(lambda t, f: jax.lax.dynamic_slice_in_dim(
        t, f * buckets, buckets, axis=0))


def _feature(config: dict, table, name: str):
    """The rows of feature ``deep/f07`` (or ``wide/f07``) of a fused table."""
    return _take_fn(config["hash_buckets"])(table,
                                            int(name.split("/f")[1]))


def load_weights(trainer, config: dict, reference, seed: int) -> list:
    """Put the benchmark's seeded weights where the Trainer keeps its own.
    The MLP leaves go into the optax parameter tree.  The per-feature tables
    are written into the program's two fused tables a feature at a time, in
    place (the table is donated to each write), so the device never holds a
    second copy of a table beside the program's state.  Returns the leaves'
    names."""
    import jax

    from tensorflowonspark_tpu.parallel.train import TrainState

    names = list(jax.eval_shape(lambda: reference.make_weights(config, seed)))
    state = trainer.state
    mlp = reference.make_weights(
        config, seed, only=tuple(n for n in names if n.startswith("mlp")))
    params = {k: dict(v) for k, v in state.params.items()}
    for name, w in mlp.items():
        layer, leaf = _mlp_path(name)
        params[layer][leaf] = w
    params = jax.device_put(params, trainer.param_shardings)

    buckets = config["hash_buckets"]
    write = jax.jit(
        lambda table, leaf, f: jax.lax.dynamic_update_slice_in_dim(
            table, leaf, f * buckets, axis=0), donate_argnums=0)
    cols = dict(state.collections)
    fused = dict(cols["embedding"])
    for kind in ("deep", "wide"):
        table = fused[kind]
        for f in range(NUM_CAT):
            leaf = reference.make_leaf(config, seed, f"{kind}/f{f:02d}")
            table = write(table, leaf, f)
            del leaf
        fused[kind] = table
    cols["embedding"] = fused
    trainer.state = TrainState(params, state.opt_state, state.step, cols)
    return names


def parameters(trainer, config: dict, names) -> dict:
    """The program's current parameters under the reference's names."""
    emb = trainer.state.collections["embedding"]
    out = {}
    for name in names:
        if name.startswith("mlp"):
            layer, leaf = _mlp_path(name)
            out[name] = trainer.state.params[layer][leaf]
        else:
            out[name] = _feature(config, emb[name.split("/")[0]], name)
    return out


def first_gradient_norms(trainer, config: dict, names) -> dict:
    """Per-leaf norm of the first gradient as the optimizers got it, worked
    out from their state after one step.  AdamW's first moment of the MLP
    is then ``(1 - b1) * g``; AdaGrad's accumulator of a table is ``g * g``,
    so the square root of its sum over a feature's rows is that feature's
    gradient norm.  Reductions only: no table-sized temporary."""
    import jax
    import jax.numpy as jnp

    buckets = config["hash_buckets"]
    acc = trainer.state.collections["embedding_opt"]

    def table_norms(a):
        per_feature = a.reshape(NUM_CAT, buckets, -1).sum(axis=(1, 2))
        return jnp.sqrt(per_feature)

    out = {}
    for kind in ("deep", "wide"):
        norms = jax.device_get(jax.jit(table_norms)(acc[f"{kind}_acc"]))
        for f in range(NUM_CAT):
            out[f"{kind}/f{f:02d}"] = float(norms[f])
    scale = 1.0 / (1.0 - config["optimizer"]["b1"])
    stack = [trainer.state.opt_state]
    while stack:
        node = stack.pop()
        if hasattr(node, "mu") and hasattr(node, "nu"):
            for name in names:
                if name.startswith("mlp"):
                    layer, leaf = _mlp_path(name)
                    out[name] = scale * float(jnp.sqrt(jnp.sum(jnp.square(
                        node.mu[layer][leaf]))))
            return {n: out[n] for n in names}
        if isinstance(node, (tuple, list)):
            stack.extend(node)
    raise RuntimeError("no Adam state in the Trainer's optimizer state")


def host_batch(columns: dict) -> dict:
    """Columns as the feed delivers them -> the step's batch (dtypes), as
    the example's ``stage`` does."""
    import numpy as np

    return {"dense": np.asarray(columns["dense"], np.float32),
            "cat": np.asarray(columns["cat"], np.int32),
            "label": np.asarray(columns["label"], np.int32)}
