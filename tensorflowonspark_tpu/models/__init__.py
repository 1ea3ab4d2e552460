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
program's counters), ``device_counters(collections, config)`` (what of
the step's collections the counters show: what the device decided) and
``counter_rows(config)`` (the rows of a collection that only those counters
read: a checkpoint written before one was counted restores with it at
zero).

The six decoders trained on packed rows — ``granite_hybrid`` (state-space
mixers and a NoPE attention layer), ``mla_moe`` (latent attention, routed
and shared experts, the multi-token-prediction module), ``lfm2_moe`` (gated
short-convolution mixers, a QK-normed RoPE attention layer, routed experts
and no shared one), ``kimi_linear`` (Kimi Delta Attention mixers beside
NoPE latent attention whose values are narrower than its keys, routed and
shared experts), ``mellum_moe`` (QK-normed RoPE attention in every layer,
three in four behind a sliding window and the fourth with YaRN frequencies
of its own, softmax-routed experts in every layer and no shared one) and
``afmoe`` (QK-normed attention with an output gate, three layers in four
behind a sliding window with RoPE and the fourth over the whole document
with no position signal, four norms a layer, a scaled embedding, leading
dense layers and then sigmoid-routed experts beside a shared one) — each
keep their ``Config``, ``ADAMW``, ``leaf_shapes``, ``layer_kinds``
(``mla_moe``: ``layer_prefixes``), mixers, ``_layer``, ``logits`` and
``batch_counters``, and share the rest:

- ``packed_decoder.py``, the skeleton: one ``Decoder`` a model, whose methods
  are the surface above under every model's name (``make_model`` — a module
  that declares its variables and, while it initialises, traces no forward
  pass —, ``make_optimizer``, ``make_loss_fn``, ``make_forward_fn``,
  ``example_batch``, ``parameter_count``, ``apply_tokens``, and for the
  five expert models ``collection_shapes``, ``device_counters`` and
  ``counter_rows``), the
  checkpointed layer loop, the feed-forward half of an expert model's layer,
  the loss over rows;
- ``packed_rows.py``, the mathematics: norm, products, SwiGLU, the positions
  inside documents and RoPE at them (plain or YaRN frequencies), the
  depthwise causal convolution that stops at a document's first token, the
  grouped-query layer (gated or not, rotated or not) and latent attention
  with or without a query latent and rotation, attention inside documents
  with or without a sliding window, the blocked loss;
- ``kernels.py``, the one seam between an algorithm and its Pallas kernels
  (``attention_pallas``, ``conv_pallas``, ``ssd_pallas``, ``kda_pallas``
  here, ``parallel/grouped_pallas.py``): ``backend()``, the rule
  ``runs_fused`` and the counter pairs ``step_counters``;
- ``parallel/moe.py``: ``routed_experts`` (top-k of a wide router, the
  experts held here, no drop) behind ``expert_ffn``, a layout's ``Routing``
  and its routing state.  (``bert`` calls the other expert layer there,
  ``moe_ffn``: Switch top-1 with a capacity, over ``ep``.)
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
    "mellum_moe": "tensorflowonspark_tpu.models.mellum_moe",
    "afmoe": "tensorflowonspark_tpu.models.afmoe",
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
