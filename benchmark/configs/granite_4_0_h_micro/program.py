"""The ``granite_4_0_h_micro`` configuration on the program's side: how the
benchmark builds the system under test for it, hands it the seeded weights a
leaf at a time, and reads back what the output check compares.  Everything
the reference must not touch lives here; the reference lives next door and
imports none of this.
"""

from __future__ import annotations


def model_config(config: dict):
    """The zoo's ``Config`` of the configuration's file: the published
    widths, the first ``num_hidden_layers`` of the published layer types,
    the vocabulary's slice."""
    from tensorflowonspark_tpu.models import granite_hybrid

    return granite_hybrid.Config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_types=tuple(
            config["layer_types"][:config["num_hidden_layers"]]),
        intermediate_size=config["shared_intermediate_size"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        mamba_n_heads=config["mamba_n_heads"],
        mamba_d_head=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"],
        mamba_n_groups=config["mamba_n_groups"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_chunk_size=config["mamba_chunk_size"],
        embedding_multiplier=config["embedding_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        attention_multiplier=config["attention_multiplier"],
        logits_scaling=config["logits_scaling"],
        rms_norm_eps=config["rms_norm_eps"], dtype=config["dtype"],
        seq_len=config["seq_len"])


def build(config: dict, ctx=None):
    """The Trainer a user's ``map_fun`` builds for this model."""
    from tensorflowonspark_tpu.models import granite_hybrid
    from tensorflowonspark_tpu.trainer import Trainer

    opt = config["optimizer"]
    recipe = dict(granite_hybrid.ADAMW, name="adamw",
                  learning_rate=opt["learning_rate"])
    if opt != recipe:
        raise ValueError(f"the program's AdamW is {recipe}, the "
                         f"configuration's file says {opt}")
    return Trainer(config["program_model"], config=model_config(config),
                   learning_rate=opt["learning_rate"],
                   error_sink=getattr(ctx, "report_error", None))


def program_name(name: str) -> str:
    """The program's flat parameter name of the reference's leaf."""
    return name.replace("/", "_")


def load_weights(trainer, config: dict, reference, seed: int) -> list:
    """Put the benchmark's seeded weights where the Trainer keeps its own,
    a leaf at a time: the Trainer's leaf is dropped before the seeded one
    is made, so the chip never holds two copies of the parameters.
    Returns the leaves' names."""
    import jax

    from tensorflowonspark_tpu.parallel.train import TrainState

    shapes = reference.leaf_shapes(config)
    names = list(shapes)
    state = trainer.state
    params = dict(state.params)
    trainer.state = state = TrainState(params, state.opt_state, state.step,
                                       state.collections)
    if set(params) != {program_name(n) for n in names}:
        raise ValueError("the program and the reference name different "
                         "leaves")
    for name in names:
        mine = program_name(name)
        old = params.pop(mine)
        if old.shape != tuple(shapes[name][0]):
            raise ValueError(f"{name}: the program's leaf is {old.shape}")
        old.delete()
        params[mine] = jax.device_put(
            reference.make_leaf(config, seed, name),
            trainer.param_shardings[mine])
    return names


def parameters(trainer, config: dict, names) -> dict:
    """The program's current parameters under the reference's names."""
    return {n: trainer.state.params[program_name(n)] for n in names}


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
            norm = jax.jit(lambda v: scale * jnp.sqrt(jnp.sum(jnp.square(v))))
            return {n: float(norm(node.mu[program_name(n)])) for n in names}
        if isinstance(node, (tuple, list)):
            stack.extend(node)
    raise RuntimeError("no Adam state in the Trainer's optimizer state")


def host_batch(columns: dict) -> dict:
    """Columns as the feed delivers them -> the step's batch (dtypes)."""
    import numpy as np

    return {"tokens": np.asarray(columns["tokens"], np.int32),
            "segment_ids": np.asarray(columns["segment_ids"], np.int32)}


def tfrecord_parse_fn(config: dict):
    """The packed row's parser: ``tokens`` and ``segment_ids`` are raw
    little-endian int32 buffers, the record's ``id`` is kept."""
    import numpy as np

    from tensorflowonspark_tpu import tfrecord

    def parse(payload: bytes):
        ex = tfrecord.decode_example(payload)
        return {
            "tokens": np.frombuffer(ex["tokens"][1][0], np.int32),
            "segment_ids": np.frombuffer(ex["segment_ids"][1][0], np.int32),
            "id": np.int64(ex["id"][1][0]),
        }

    return parse
