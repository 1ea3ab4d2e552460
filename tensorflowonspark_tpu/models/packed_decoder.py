"""The registry's side of a decoder trained on packed rows, once
(``granite_hybrid``, ``mla_moe``, ``lfm2_moe``, ``kimi_linear``,
``mellum_moe``, ``afmoe``).

A decoder's module keeps what is its own — ``Config``, ``ADAMW``,
``leaf_shapes``, its ``layer_kinds``, its mixers, its ``_layer`` (which names
the ``jax.named_scope``s), its ``logits``, its ``batch_counters`` — and
describes itself in one :class:`Decoder`, whose methods it binds to the names
the registry promises (``models/__init__.py``: ``make_model =
_DECODER.make_model`` and so on, a dozen lines at the end of each of the six).

What is here has one body for the six: the loop over the layers (a layer's
leaves sliced by prefix, the layer recomputed in the backward pass but for
attention's output and log-sum-exp and what the model names to keep
besides, the routing bias's row threaded to an expert layer and its counts
gathered: :func:`run_layer` is the one-layer form), the
feed-forward half of an expert model's layer with its leaves' shapes
(:func:`feed_forward`; where a layout norms what a half adds,
:func:`add_normed`), the next-token loss over rows a block of tokens at a
time (:func:`loss_sums`), the initializers two models or more draw from, the
flax module, the optimizer, the example rows, the parameter count, the
stateful loss and forward wrappers, and what of the routing state the
program's counters show.  A recomputation policy, a batch axis or one
``jax.jit`` a layer shape is written here, once.

What holds for all six and is no option of any: parameters are float32 (a
flat dict; the flax module only declares them and the ``moe`` collection,
and while it initialises traces no forward pass), activations
``Config.dtype``, the mathematics pure functions over the dict; every layer
is recomputed in the backward pass but for what attention names
(``packed_rows.ATTENTION_SAVED``: its forward blocks run once a step),
attention runs a block of queries at a time and the loss a block of tokens
at a time.  An expert model is told which of its router's experts this chip
holds (``Config.experts_held``, all unless told): the router stays as wide
as published, the held experts' part is computed
(``parallel/moe.py::routed_experts``) and what the others would have added
is left out; no exchange runs and none is stood in for.  What takes no
gradient — the correction biases, the counts behind them — is the ``moe``
collection, which the Trainer's stateful step threads and checkpoints (one
data shard is what has run: ``ROADMAP.md`` B).

JAX is imported where it is used, as in the models.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable

import numpy as np

from tensorflowonspark_tpu.models.packed_rows import (
    ATTENTION_SAVED, blocked_cross_entropy, document_positions, example_rows,
    loss_positions, rms, swiglu)


#: the collection of a routed model's non-gradient state
#: (``moe.routing_state_shapes``)
COLLECTION = "moe"


@functools.lru_cache(maxsize=None)
def _keeping(names: tuple):
    """The recomputation policy that keeps ``names``, made once: a
    ``jax.jit`` inside a layer (the routed part, a kernel's call) is split
    into what is kept and what is made again once a policy *object*, so the
    layers share one traced and lowered function of it only while they
    share the policy (``tests/test_moe_grouped.py`` counts the functions a
    step holds: a warm start pays for each)."""
    import jax

    return jax.checkpoint_policies.save_only_these_names(*names)


def run_layer(layer, params, prefix: str, kinds, config, x, seg, pos, bias,
              scopes: tuple = (), saved: tuple = ()):
    """One layer on a batch of rows, recomputed in the backward pass:
    ``layer(*kinds, prefix, config, scopes, leaves, x, seg, pos, bias) ->
    (x, counts)`` of the leaves of ``params`` under ``prefix``, ``x`` (B, T,
    D), ``seg`` (B, T), ``pos`` the positions inside documents and ``bias``
    the routing bias's row (each None where the model has none).
    ``scopes``: the named scopes the caller has opened round the layer (a
    custom backward pass opens them again); ``saved``: what the layer names
    (``checkpoint_name``) for the recomputation to keep, besides what
    attention names in every layer that has it
    (``packed_rows.ATTENTION_SAVED``)."""
    import jax

    mine = {k: v for k, v in params.items() if k.startswith(prefix)}
    return jax.checkpoint(
        functools.partial(layer, *kinds, prefix, config, scopes),
        policy=_keeping((*ATTENTION_SAVED, *saved)))(mine, x, seg, pos, bias)


def loss_sums(logits, states, tokens, seg, want: int, ahead: int = 1):
    """``(sum of the cross-entropies, positions counted)`` of a batch of
    packed rows: position ``t`` of ``states`` (B, T, D) is scored by
    ``logits`` (a block of states -> float32 logits) against the token
    ``ahead`` places on, where that token and every one between are ``t``'s
    document's.  The logits exist a block of at most ``want`` tokens at a
    time, under the ``jax.named_scope`` ``lm_head``."""
    import jax
    import jax.numpy as jnp

    def row(xr, u, s):
        valid = loss_positions(s, ahead)
        return blocked_cross_entropy(xr, logits, jnp.roll(u, -ahead), valid,
                                     want), jnp.sum(valid)

    with jax.named_scope("lm_head"):
        total, count = jax.vmap(row)(states, tokens, seg)
    return jnp.sum(total), jnp.sum(count)


def ffn_leaf_shapes(p: str, ffn: str, d: int, dense: int, experts: int,
                    routing, shared: int = 0) -> dict:
    """Name -> shape of the leaves :func:`feed_forward` reads under the
    prefix ``p``, in forward order: a ``"dense"`` SwiGLU ``dense`` wide, or
    a router, a shared expert ``shared`` wide (0: none) and the held
    experts, each ``experts`` wide."""
    if ffn == "dense":
        return {p + "mlp_gate": (d, dense), p + "mlp_up": (d, dense),
                p + "mlp_down": (dense, d)}
    held = len(routing.held)
    out = {p + "router": (d, routing.n_experts)}
    if shared:
        out.update({p + "shared_gate": (d, shared),
                    p + "shared_up": (d, shared),
                    p + "shared_down": (shared, d)})
    out.update({p + "experts_gate": (held, d, experts),
                p + "experts_up": (held, d, experts),
                p + "experts_down": (held, experts, d)})
    return out


#: what a half of a layer that norms what it adds names of it
#: (``checkpoint_name``): the mixer's and the feed-forward's result before
#: their post-norms, whose backward pass reads them.  A model that lists one
#: under ``Decoder.saved`` keeps it and does not make it a second time.
MIXER_ADDED, FFN_ADDED = "mixer_added", "ffn_added"


def add_normed(x, y, scale, eps: float, name: str | None = None):
    """``x + y`` or, where the layout norms what a half of a layer adds
    (``scale`` its post-norm's leaf; None: it has none), ``x + rms(y;
    scale)`` under the ``jax.named_scope`` ``post_norm``, ``y`` named
    ``name`` for a recomputation's policy."""
    import jax
    from jax.ad_checkpoint import checkpoint_name

    if scale is None:
        return x + y
    with jax.named_scope("post_norm"):
        return x + rms(checkpoint_name(y, name) if name else y, scale, eps)


def feed_forward(lp, prefix: str, ffn: str, x, bias, eps: float, routing, *,
                 shared: bool = False, scopes: tuple = (),
                 norm: str = "norm2", post_norm: str | None = None):
    """The second half of an expert model's layer on a batch of rows:
    ``(x + FFN(rms(x)), counts)``, the norm's leaf ``norm`` or, where the
    layout norms the result too (``post_norm`` names that leaf), ``(x +
    rms(FFN(rms(x))), counts)`` (:func:`add_normed`).  ``ffn`` ``"dense"``:
    the SwiGLU of ``mlp_{gate,up,down}`` under the ``jax.named_scope``
    ``mlp``, ``counts`` (E,) zeros; ``"experts"``: ``moe.expert_ffn`` of the
    layout's ``routing`` (with a ``shared`` expert or without, the layer's
    ``bias`` row, under the caller's ``scopes``) and the tokens that chose
    each expert."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.parallel import moe

    h = rms(x, lp[prefix + norm], eps).reshape(-1, x.shape[-1])
    if ffn == "dense":
        with jax.named_scope("mlp"):
            y = swiglu(h, lp[prefix + "mlp_gate"], lp[prefix + "mlp_up"],
                       lp[prefix + "mlp_down"])
        counts = jnp.zeros((routing.n_experts,), jnp.int32)
    else:
        y, counts = moe.expert_ffn(lp, prefix, h, bias, routing,
                                   shared=shared, scopes=scopes)
    return add_normed(x, y.reshape(x.shape),
                      lp[prefix + post_norm] if post_norm else None,
                      eps, FFN_ADDED), counts


def normals(std: float, layers: int) -> tuple:
    """``(normal(std), the same over sqrt(2 layers))``: the matrices that
    write into the residual stream start smaller, by the layers that add to
    it (GPT-2's and Megatron-LM's scaled initialisation): at one size for
    all, every token's hidden state is one shared vector after a layer and
    a seeded router sends a row's tokens to the same few experts."""
    import flax.linen as nn

    return (nn.initializers.normal(std),
            nn.initializers.normal(std / math.sqrt(2 * max(layers, 1))))


def conv_taps(taps: int):
    """Uniform in ±1/sqrt(``taps``), as PyTorch's ``Conv1d`` leaves a
    depthwise convolution's taps and bias."""
    import jax

    bound = 1.0 / math.sqrt(taps)
    return lambda key, shape, dtype: jax.random.uniform(
        key, shape, dtype, -bound, bound)


def a_log(key, shape, dtype):
    """``log U(1, 16)``: Mamba-2's published draw, which KDA's code keeps."""
    import jax
    import jax.numpy as jnp

    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def dt_bias(key, shape, dtype):
    """The inverse softplus of log-uniform [1e-3, 1e-1] (Mamba-2's)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def embed(params, tokens, config, multiplier: float | None = None):
    """``E[u]`` in the activations' type; times ``multiplier`` first, in
    the table's float32, where the layout scales its embedding (under the
    ``jax.named_scope`` ``embed_scale``)."""
    import jax
    import jax.numpy as jnp

    x = jnp.take(params["embed"], tokens, axis=0)
    if multiplier is not None:
        with jax.named_scope("embed_scale"):
            x = x * multiplier
    return x.astype(jnp.dtype(config.dtype))


@dataclasses.dataclass(frozen=True)
class Decoder:
    """What a decoder's module tells the skeleton (functions of its
    ``Config`` unless said otherwise)."""
    adamw: dict                 # the recipe ``make_optimizer`` builds
    leaf_shapes: Callable       # -> {name: shape}, in forward order
    #: -> [(prefix, *kinds)] of the layers the loop runs, in forward order;
    #: a layer whose last kind is "experts" takes a row of the routing bias
    #: and gives the tokens that chose each expert (where the model has
    #: ``gauges``: a tuple, the gauges' readings after them)
    layers: Callable
    layer: Callable             # :func:`run_layer`'s ``layer``
    logits: Callable            # (params, states, config) -> float32 logits
    init: Callable              # -> ((name, shape) -> a leaf's initializer)
    routing: Callable | None = None     # -> ``moe.Routing``; None: no router
    #: (params, bias, tokens, seg, config) -> (sum, positions, ..., counts):
    #: the model's own where it scores more than the next token, each later
    #: (sum, positions) weighted by ``later_weights``
    loss_terms: Callable | None = None
    later_weights: Callable = lambda config: ()
    embed: Callable = embed     # (params, tokens, config) -> (B, T, D)
    positions: bool = False     # the layers read positions inside documents
    saved: tuple = ()           # names a layer's recomputation keeps
    #: what an expert layer measures of itself beside its counts, an int32
    #: scalar each: the collection's row (expert layers,) that adds a
    #: gauge up -> the program's counter that shows the row
    gauges: dict = dataclasses.field(default_factory=dict)
    example_tokens: Callable = lambda config: 64    # an example row's, at most

    def hidden_states(self, params, bias, tokens, seg, config):
        """``(x, pos, counts)``: the hidden states before the last norm (B,
        T, D), the positions inside documents (None where no layer reads
        them) and a (E,) count an expert layer (a list, forward order);
        ``bias`` (expert layers, E) enters the experts' choice."""
        import jax

        pos = jax.vmap(document_positions)(seg) if self.positions else None
        x = self.embed(params, tokens, config)
        counts = []
        for prefix, *kinds in self.layers(config):
            experts = kinds[-1] == "experts"
            x, c = run_layer(self.layer, params, prefix, kinds, config, x,
                             seg, pos, bias[len(counts)] if experts else None,
                             saved=self.saved)
            if experts:
                counts.append(c)
        return x, pos, counts

    def apply_tokens(self, params, bias, tokens, segment_ids, config):
        """Teacher-forced forward: (B, T) tokens and segment ids -> (B, T,
        V) float32 logits."""
        import jax

        x, _, _ = self.hidden_states(params, bias, tokens, segment_ids,
                                     config)
        with jax.named_scope("lm_head"):
            return jax.vmap(lambda xr: self.logits(params, xr, config))(x)

    def next_token_terms(self, params, bias, tokens, segment_ids, config):
        """``(sum of the cross-entropies, positions counted, counts)`` of a
        batch of packed rows (:func:`loss_sums`); ``counts`` (expert layers,
        E) int32 in forward order (``[]`` for a model with no router; with
        the ``gauges``' (expert layers,) after it, a tuple, where the model
        has some)."""
        import jax
        import jax.numpy as jnp

        x, _, counts = self.hidden_states(params, bias, tokens, segment_ids,
                                          config)
        if self.routing is not None and counts:
            counts = jax.tree.map(lambda *rows: jnp.stack(rows), *counts)
        elif self.routing is not None:     # no layer of the run has a router
            counts = jnp.zeros((0, self.routing(config).n_experts), jnp.int32)
            if self.gauges:
                counts = (counts, *(jnp.zeros((0,), jnp.int32)
                                    for _ in self.gauges))
        return (*loss_sums(lambda xb: self.logits(params, xb, config), x,
                           tokens, segment_ids, config.loss_block), counts)

    def _loss(self, params, bias, batch, config):
        """``(loss, counts)``: every term's mean over its counted
        positions, the later ones weighted."""
        import jax.numpy as jnp

        *sums, counts = (self.loss_terms or self.next_token_terms)(
            params, bias, batch["tokens"], batch["segment_ids"], config)
        loss = sums[0] / jnp.maximum(sums[1], 1)
        for weight, total, n in zip(self.later_weights(config), sums[2::2],
                                    sums[3::2]):
            loss = loss + weight * total / jnp.maximum(n, 1)
        return loss, counts

    def collection_shapes(self, config) -> dict:
        """The ``moe`` collection: a row an expert layer, in forward order
        (``moe.routing_state_shapes``, and an int32 a layer for every one
        of ``gauges``)."""
        from tensorflowonspark_tpu.parallel import moe

        routing = self.routing(config)
        return {**moe.routing_state_shapes(routing.n_experts, routing.layers),
                **{row: ((routing.layers,), "int32") for row in self.gauges}}

    def step_collection(self, state: dict, counts, config, tokens: int):
        """The routing collection after a step whose ``tokens`` tokens chose
        ``counts`` (expert layers, E): ``moe.step_routing_state``, and every
        gauge's readings (they follow ``counts`` in a tuple) added to its
        row."""
        from tensorflowonspark_tpu.parallel import moe

        counts, *readings = counts if self.gauges else (counts,)
        routing = self.routing(config)
        return {**moe.step_routing_state(
            state, counts, routing.held, top_k=routing.top_k,
            speed=routing.speed, tokens=tokens),
                **{row: state[row] + reading
                   for row, reading in zip(self.gauges, readings)}}

    def make_model(self, config, mesh=None):
        """The flax module: it declares every leaf of ``leaf_shapes`` and
        the collection's variables and calls :meth:`apply_tokens`; while it
        initialises it only declares (``init``'s callers read the variables
        and nothing else, so no forward pass is traced for them)."""
        import flax.linen as nn
        import jax.numpy as jnp

        decoder, init = self, self.init(config)
        shapes = self.leaf_shapes(config)
        state = self.collection_shapes(config) if self.routing else {}

        class PackedDecoder(nn.Module):
            @nn.compact
            def __call__(self, tokens, segment_ids):
                params = {name: self.param(name, init(name, shape), shape,
                                           jnp.float32)
                          for name, shape in shapes.items()}
                held = {name: self.variable(COLLECTION, name, jnp.zeros,
                                            *spec)
                        for name, spec in state.items()}
                if self.is_initializing():
                    return jnp.zeros(tokens.shape + (config.vocab_size,),
                                     jnp.float32)
                return decoder.apply_tokens(
                    params, held["bias"].value if held else None, tokens,
                    segment_ids, config)

        return PackedDecoder()

    def make_optimizer(self, config, learning_rate: float):
        import optax

        return optax.adamw(learning_rate, **self.adamw)

    def make_loss_fn(self, module, config):
        """:meth:`_loss` as the Trainer calls it.  A model with a router:
        ``loss(params, collections, batch) -> (loss, new collections)``, the
        routing collection moved on a step; one without: ``loss(params,
        batch)``."""
        if self.routing is None:
            return lambda params, batch: self._loss(params, None, batch,
                                                    config)[0]

        def loss_fn(params, collections, batch):
            state = collections[COLLECTION]
            loss, counts = self._loss(params, state["bias"], batch, config)
            return loss, {**collections, COLLECTION: self.step_collection(
                state, counts, config, batch["tokens"].size)}

        loss_fn.stateful = True
        return loss_fn

    def make_forward_fn(self, module, config):
        if self.routing is None:
            return lambda params, batch: self.apply_tokens(
                params, None, batch["tokens"], batch["segment_ids"], config)

        def forward(params, collections, batch):
            return self.apply_tokens(
                params, collections[COLLECTION]["bias"], batch["tokens"],
                batch["segment_ids"], config)

        forward.stateful = True
        return forward

    def device_counters(self, collections, config) -> dict:
        """What the device decided, for the program's counters
        (``moe.routing_counters`` of the routing collection, and the
        ``gauges``' rows under their counters' names)."""
        from tensorflowonspark_tpu.parallel import moe

        state = collections[COLLECTION]
        return {**moe.routing_counters(state, self.routing(config).held),
                **{name: state[row] for row, name in self.gauges.items()}}

    def counter_rows(self, config) -> dict:
        """The rows of the collections that only the counters read
        (``moe.COUNTER_ROWS`` and the ``gauges``'): what a checkpoint may
        lack and still be restored, the row at zero."""
        from tensorflowonspark_tpu.parallel import moe

        return {COLLECTION: (*moe.COUNTER_ROWS, *self.gauges)}

    def parameter_count(self, config) -> int:
        return sum(int(np.prod(s))
                   for s in self.leaf_shapes(config).values())

    def example_batch(self, config, batch_size: int = 8, seed: int = 0,
                      seq_len: int | None = None):
        """Packed rows of two documents each, ``seq_len`` tokens (at most
        ``example_tokens`` unless told: a step compiles at the shape it is
        fed)."""
        return example_rows(
            config.vocab_size, batch_size, seed,
            int(seq_len or min(config.seq_len, self.example_tokens(config))))
