"""Model zoo mirroring the reference's ``examples/`` coverage, TPU-first.

Reference anchor: ``examples/`` (mnist, cifar10, imagenet/inception+resnet,
criteo wide&deep in the estimator era; see ``SURVEY.md §1 L6``).  The
reference ships these as free-standing TF scripts; here they are library
models (flax.linen) so the same definitions serve the examples, the
pipeline API, the benchmarks, and the graft entry point.

Every model module exposes the same surface:

- ``Config`` dataclass (tiny test config via ``Config.tiny()``)
- ``make_model(config, mesh=None)`` → flax module (mesh enables sp/ring
  attention where it applies)
- ``make_loss_fn(module, config)`` → ``loss(params, batch) -> scalar``
- ``example_batch(config, batch_size, seed)`` → dict of numpy arrays
- ``SEQUENCE_AXES`` → dict leaf-name → axis index sharded over ``sp``

Optional hooks the Trainer looks for: ``make_optimizer``,
``make_sharded_train_step``, ``make_collection_shardings``,
``batch_counters(batch, config)`` (what a step's host batch adds to the
program's counters) and ``device_counters(collections, config)`` (what of
the step's collections the counters show: what the device decided).

The four decoders trained on packed rows, ``granite_hybrid`` (state-space
mixers and a NoPE attention layer), ``mla_moe`` (latent attention, routed
and shared experts, the multi-token-prediction module), ``lfm2_moe``
(gated short-convolution mixers, a QK-normed RoPE attention layer, routed
experts and no shared one) and ``kimi_linear`` (Kimi Delta Attention mixers
— a channel-wise gated delta rule computed in chunks — beside NoPE latent
attention whose values are narrower than its keys, routed and shared
experts), share ``packed_rows.py``: norm, products, SwiGLU, the positions
inside documents and RoPE at them (``mla_moe``, ``lfm2_moe``), the depthwise
causal convolution that stops at a document's first token
(``granite_hybrid``, ``lfm2_moe``, ``kimi_linear``), latent attention with or
without a query latent and rotation (``mla_moe``, ``kimi_linear``), attention
inside documents (as ``jnp`` code or, on a TPU where a head fills whole
lanes, the Pallas kernels of ``attention_pallas.py``; granite's scan has
``ssd_pallas.py``), the blocked loss.  Two expert layers live in
``parallel/moe.py``: ``bert`` calls ``moe_ffn`` (Switch top-1 with a
capacity, over ``ep``), ``mla_moe``, ``lfm2_moe`` and ``kimi_linear`` call
``routed_experts`` (top-k of a wide router, the experts held here, no drop,
the work sized to a step's own count of slots that landed here) and keep its
routing state — correction biases, their update, the counts the program's
counters show — by the same three functions there.
"""

from __future__ import annotations

import importlib

_REGISTRY = {
    "mnist_mlp": "tensorflowonspark_tpu.models.mnist",
    "cifar10_cnn": "tensorflowonspark_tpu.models.cifar",
    "resnet50": "tensorflowonspark_tpu.models.resnet",
    "inception_v3": "tensorflowonspark_tpu.models.inception",
    "mobilenet_v1": "tensorflowonspark_tpu.models.mobilenet",
    "wide_deep": "tensorflowonspark_tpu.models.widedeep",
    "bert": "tensorflowonspark_tpu.models.bert",
    "tiny_lm": "tensorflowonspark_tpu.models.tinylm",
    "granite_hybrid": "tensorflowonspark_tpu.models.granite_hybrid",
    "mla_moe": "tensorflowonspark_tpu.models.mla_moe",
    "lfm2_moe": "tensorflowonspark_tpu.models.lfm2_moe",
    "kimi_linear": "tensorflowonspark_tpu.models.kimi_linear",
}


def get_model(name: str):
    """Return the model module registered under ``name``."""
    if name not in _REGISTRY:
        raise KeyError(f"unknown model {name!r}; available: {sorted(_REGISTRY)}")
    return importlib.import_module(_REGISTRY[name])


def available() -> list[str]:
    return sorted(_REGISTRY)


def _model_inputs(batch: dict) -> tuple:
    """Positional model inputs from an example batch (labels stripped —
    the shape-policy module's one label-key convention)."""
    from tensorflowonspark_tpu import shapes

    return tuple(v for k, v in batch.items() if k not in shapes.LABEL_KEYS)


def _is_tiny(params, lib) -> bool:
    """Heuristic: does the restored pytree match the zoo's tiny config?

    Compares leaf count+shapes against ``Config.tiny()``'s abstract init so
    transform works for both test-sized and full-sized exports without the
    caller having to pass a config through.
    """
    import jax

    from tensorflowonspark_tpu.parallel.train import unbox

    try:
        tiny = lib.Config.tiny()
        module = lib.make_model(tiny)
        batch = lib.example_batch(tiny, batch_size=1)
        shapes = jax.eval_shape(
            lambda: module.init(jax.random.PRNGKey(0), *_model_inputs(batch))
        )
        tiny_leaves = [
            tuple(l.shape)
            for l in jax.tree_util.tree_leaves(unbox(shapes)["params"])
        ]
        real_leaves = [
            tuple(getattr(l, "shape", ()))
            for l in jax.tree_util.tree_leaves(params)
        ]
        return sorted(tiny_leaves) == sorted(real_leaves)
    except Exception:
        return False
