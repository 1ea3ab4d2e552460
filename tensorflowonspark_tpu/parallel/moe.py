"""Two mixture-of-experts feed-forward layers.

**:func:`moe_ffn` — Switch top-1 with a capacity, expert-parallel over the
``ep`` mesh axis** (called by ``models/bert.py`` where ``moe_experts`` > 0).
Reference anchor: **absent from the reference** (``SURVEY.md §2.3``: EP
"NO — out of scope for parity") — a beyond-parity capability completing the
framework's parallelism families (dp/fsdp/tp/sp/pp/**ep**).

- **Router**: top-1 gating in float32; each token goes to its argmax
  expert, bounded by a per-expert **capacity** ``C = capacity_factor ×
  tokens / E`` (static shape — XLA needs it).  Tokens beyond an expert's
  capacity are *dropped* (contribute zero; the residual connection carries
  them), the standard Switch behavior.
- **Dispatch/combine as einsums, not gathers**: the one-hot dispatch tensor
  ``(tokens, E, C)`` turns routing into three MXU matmuls —
  ``dispatch·x → (E, C, M)``, the expert FFN, ``combine·out → (tokens, M)``
  — exactly the formulation XLA shards well.  The expert dim of both the
  dispatched activations and the expert weights carries the ``"expert"``
  logical axis (→ ``ep``, ``mesh.DEFAULT_RULES``), so GSPMD inserts the
  token all_to_alls over ``ep`` on its own; there are no hand-written
  collectives to get wrong.
- **Load-balancing aux loss** (Switch eq. 4): ``E · Σ_e f_e · p_e`` where
  ``f_e`` is the fraction of tokens routed to expert ``e`` and ``p_e`` the
  mean router probability — minimised at uniform routing.  Returned to the
  caller; model code sows it and the loss adds ``aux_weight ×`` it.

Layout contract: tokens ``(T, M)`` in, experts' weights ``(E, M, H)`` /
``(E, H, M)``.  ``T`` must be divisible by nothing in particular (capacity
handles imbalance), but shard the token dim over the data axes as usual.

**:func:`routed_experts` — top-k of a wide router, the experts held here,
no token dropped** (the DeepSeek-V3 layout, arXiv:2412.19437; called through
:func:`expert_ffn` by ``models/mla_moe.py``, ``lfm2_moe.py`` and
``kimi_linear.py``, each of which names its layout in a :class:`Routing`).
The layer is told *which* of the router's experts this chip holds.  It
scores every token against all of them (sigmoid, or a softmax over all of
them where the layout's is one; float32), chooses the
``top_k`` of score plus a correction bias that takes no gradient, weighs the
chosen by their normalised scores, keeps every slot (token, choice) whose
expert is held — any number, from none to all — sorts the kept slots by
expert, the held experts' first, multiplies those expert by expert as
grouped products, and adds the results back weighted.  A grouped product is
one algorithm with two executions (:func:`grouped_runs_fused` is the rule,
and the models' steps count which applied:
``moe_grouped_fused_steps_total`` / ``moe_grouped_plain_steps_total``): on a
TPU, at shapes that fill their tiles, in the forms a step takes when its
slots fit, the Pallas kernels of ``grouped_pallas`` walk the row tiles that
hold a live row and no others; anywhere else and in the form a layer takes
when its slots overflow ``jax.lax.ragged_dot`` runs, which is also the
kernels' oracle.  The slots that landed here are a prefix of the sorted
order whose length the device knows after the router: everything after the
router is one function of a static row count, traced at three sizes
(:func:`row_sizes`: :func:`tight_rows`, half over an even router's
share; :func:`prefix_rows`, three times it; all the slots), and a
``jax.lax.switch`` takes the smallest that the step's count fits — so
gathers, products, masks and sums work on little more than the rows that
landed here, whatever the router's balance, and a step that overflows is
still exact.  What the
experts held elsewhere would have added is left out; on one chip no exchange
runs.  It returns how many tokens chose each of the router's experts, which
the caller's bias update and counters read.  No capacity factor exists and
no auxiliary loss.  What such a model keeps beside its parameters is
:func:`routing_state_shapes`, :func:`step_routing_state` and
:func:`routing_counters`.
"""

from __future__ import annotations

import functools
import logging
from typing import Any, Mapping, NamedTuple

from tensorflowonspark_tpu.models.kernels import runs_fused, step_counters

logger = logging.getLogger(__name__)

#: what a layout's router makes of its logits (:class:`Routing`'s ``score``)
SCORES = ("sigmoid", "softmax")

#: flax logical axes for each param — models pass these to
#: ``nn.with_partitioning`` so ``param_sharding_from_metadata`` maps the
#: expert dim onto ``ep`` and the ffn dim onto ``tp``
PARAM_AXES = {
    "gate": ("embed", "expert"),
    "w_in": ("expert", "embed", "mlp"),
    "b_in": ("expert", "mlp"),
    "w_out": ("expert", "mlp", "embed"),
    "b_out": ("expert", "embed"),
}


def capacity_of(num_tokens: int, num_experts: int,
                capacity_factor: float) -> int:
    """Static per-expert capacity (≥ 1)."""
    return max(1, int(num_tokens * capacity_factor / num_experts))


def top1_route(logits, capacity: int, token_mask=None):
    """Switch top-1 routing → (dispatch, combine, aux_loss).

    ``logits``: (T, E) float32 router scores.  ``token_mask``: optional
    (T,) 1.0/0.0 — masked-out (padding) tokens are NOT routed: they claim
    no capacity slot (so a short sequence's pads can't crowd out a later
    sequence's real tokens), produce zero output (the residual carries
    them), and are excluded from the load-balance statistics.  Returns

    - ``dispatch``: (T, E, C) one-hot — token t occupies slot c of expert e
      (all-zero row = dropped or padding token),
    - ``combine``: ``dispatch`` scaled by the router probability,
    - ``aux``: the Switch load-balancing scalar (see module docstring).
    """
    import jax
    import jax.numpy as jnp

    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)                     # (T,)
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)   # (T, E)
    if token_mask is not None:
        onehot = onehot * token_mask.astype(jnp.float32)[:, None]

    # slot within the chosen expert: 0-based running count of earlier
    # tokens routed to the same expert (token order = slot order; padding
    # rows are all-zero in ``onehot`` and advance no counter)
    position = jnp.cumsum(onehot, axis=0) * onehot - onehot     # (T, E)
    keep = (position < capacity).astype(jnp.float32) * onehot
    slot = jax.nn.one_hot(
        jnp.sum(position, axis=-1).astype(jnp.int32), capacity,
        dtype=jnp.float32)                                      # (T, C)
    dispatch = keep[:, :, None] * slot[:, None, :]              # (T, E, C)
    gate_prob = jnp.sum(probs * onehot, axis=-1)                # (T,)
    combine = dispatch * gate_prob[:, None, None]

    # load balance: fraction routed vs mean probability, per expert —
    # means over REAL tokens only
    if token_mask is None:
        n_real = jnp.float32(t)
        f = onehot.sum(axis=0) / n_real                         # (E,)
        p = probs.mean(axis=0)                                  # (E,)
    else:
        tm = token_mask.astype(jnp.float32)
        n_real = jnp.maximum(tm.sum(), 1.0)
        f = onehot.sum(axis=0) / n_real
        p = (probs * tm[:, None]).sum(axis=0) / n_real
    aux = e * jnp.sum(f * p)
    return dispatch, combine, aux


def group_count(num_tokens: int, group_size: int) -> int:
    """Number of routing groups: tokens split into equal groups of at most
    ``group_size`` — the largest divisor of ``num_tokens`` that fits.

    Token counts with no divisor near ``group_size`` (worst case: prime
    ``num_tokens`` → groups of 1) silently disable the per-group capacity
    bound and degenerate the load-balance aux (ADVICE r5).
    :func:`moe_ffn` avoids the trap by padding the token dim up to a
    multiple of the group size before calling this; direct callers that
    hit the collapse get a structured warning event
    (``moe.group_size_collapsed``) + log line so the degradation is
    visible instead of silent.
    """
    ideal = min(num_tokens, max(1, group_size))
    tg = ideal
    while num_tokens % tg:
        tg -= 1
    if tg < max(1, ideal // 2) and num_tokens > 1:
        from tensorflowonspark_tpu import obs

        obs.event("moe.group_size_collapsed", num_tokens=num_tokens,
                  requested_group_size=group_size, actual_group_size=tg)
        logger.warning(
            "moe.group_count: %d tokens have no divisor near group_size=%d "
            "(groups of %d); the per-group capacity bound is effectively "
            "disabled — pad the token count to a multiple of the group "
            "size (moe_ffn does this automatically)",
            num_tokens, group_size, tg)
    return num_tokens // tg


def moe_ffn(x, params: Mapping[str, Any], *, capacity_factor: float = 1.25,
            activation=None, token_mask=None, group_size: int = 1024):
    """Expert-parallel FFN over tokens ``x`` of shape ``(..., M)``.

    ``params``: the :data:`PARAM_AXES` pytree — ``gate (M, E)``,
    ``w_in (E, M, H)``, ``b_in (E, H)``, ``w_out (E, H, M)``,
    ``b_out (E, M)``.  ``token_mask``: optional, shaped like ``x`` minus
    the feature dim — 0 marks padding tokens, which are not routed (see
    :func:`top1_route`).  Returns ``(y, aux_loss)`` with ``y`` shaped like
    ``x``; the caller adds the residual and weighs ``aux_loss`` into the
    objective.  Computation follows the house MXU policy: matmuls in the
    input dtype with float32 accumulation; router math fully float32.

    Routing runs per **token group** of ≤ ``group_size`` tokens (standard
    Switch/Mesh-TF practice): the dispatch/combine tensors are
    ``(G, Tg, E, C)`` with ``C = capacity_factor·Tg/E``, i.e. memory
    ``O(T·Tg)`` — *linear* in the global token count for a fixed group
    size, where one global group would be quadratic (B=32, S=384 BERT
    shapes: ~63 MB vs ~755 MB per MoE layer) — and the capacity bound +
    load-balance aux apply within each group.  Token order is preserved;
    batches ≤ ``group_size`` tokens route exactly as a single group.

    Token counts that do not divide into groups of the requested size
    (worst case: prime ``T``, whose only divisors are 1 and ``T``) are
    **padded** up to the next multiple of the group size — pads are
    masked out of routing (zero capacity claimed, zero output, excluded
    from the aux statistics) and sliced off the result — instead of
    letting ``group_count`` degenerate to tiny groups that silently
    disable the capacity bound (ADVICE r5).  Padding is trace-time
    (static shapes), so it costs one concat/slice pair per call only
    when actually needed.
    """
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.parallel import mesh as mesh_lib

    if activation is None:
        import flax.linen as nn

        activation = nn.gelu

    import math

    dtype = x.dtype
    lead = x.shape[:-1]
    m = x.shape[-1]
    t = math.prod(lead)
    tg_ideal = min(t, max(1, group_size))
    pad = (-t) % tg_ideal
    x_flat = x.reshape(t, m)
    mask_flat = None if token_mask is None else token_mask.reshape(t)
    if pad:
        x_flat = jnp.concatenate(
            [x_flat, jnp.zeros((pad, m), x_flat.dtype)])
        mask_flat = jnp.concatenate([
            jnp.ones(t, jnp.float32) if mask_flat is None
            else mask_flat.astype(jnp.float32),
            jnp.zeros(pad, jnp.float32),
        ])
    t_padded = t + pad
    g = t_padded // tg_ideal
    xt = x_flat.reshape(g, tg_ideal, m)                         # (G, Tg, M)
    e = params["w_in"].shape[0]
    c = capacity_of(tg_ideal, e, capacity_factor)

    grouped_mask = (None if mask_flat is None
                    else mask_flat.reshape(g, tg_ideal))        # (G, Tg)
    logits = jnp.einsum("gtm,me->gte", xt.astype(jnp.float32),
                        params["gate"].astype(jnp.float32))
    if grouped_mask is None:
        dispatch, combine, aux = jax.vmap(
            lambda lg: top1_route(lg, c))(logits)
    else:
        dispatch, combine, aux = jax.vmap(
            lambda lg, mg: top1_route(lg, c, token_mask=mg))(
                logits, grouped_mask)

    # (G, E, C, M): each expert's padded token block per group — sharded
    # over ep so the expert matmuls (and the all_to_alls feeding them) run
    # expert-parallel
    expert_in = jnp.einsum("gtec,gtm->gecm", dispatch.astype(dtype), xt,
                           preferred_element_type=jnp.float32).astype(dtype)
    active = mesh_lib.get_active_mesh()
    if active is not None and active.shape.get("ep", 1) > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P

        # pin ONLY the expert dim (that is what forces the token
        # all_to_all over ep); the group/capacity/model dims stay
        # UNCONSTRAINED — a None here would mean "replicated" and would
        # all_gather every group onto every dp/fsdp rank, making each
        # data-parallel rank compute the global batch's expert FFNs
        u = P.UNCONSTRAINED
        expert_in = jax.lax.with_sharding_constraint(
            expert_in, NamedSharding(active, P(u, "ep", u, u)))
    h = activation(
        jnp.einsum("gecm,emh->gech", expert_in, params["w_in"].astype(dtype),
                   preferred_element_type=jnp.float32).astype(dtype)
        + params["b_in"].astype(dtype)[None, :, None, :])
    out = jnp.einsum("gech,ehm->gecm", h, params["w_out"].astype(dtype),
                     preferred_element_type=jnp.float32).astype(dtype)
    out = out + params["b_out"].astype(dtype)[None, :, None, :]
    y = jnp.einsum("gtec,gecm->gtm", combine.astype(dtype), out,
                   preferred_element_type=jnp.float32).astype(dtype)
    y = y.reshape(t_padded, m)
    if pad:
        y = y[:t]  # padding tokens produced zeros; drop them
    return y.reshape(*lead, m), aux.mean()


def init_params(rng, num_experts: int, model_dim: int, hidden_dim: int,
                dtype=None):
    """Plain (non-flax) param pytree for :func:`moe_ffn` — used by tests
    and by callers outside the flax module system."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32
    k1, k2, k3 = jax.random.split(rng, 3)
    scale_in = (2.0 / model_dim) ** 0.5
    scale_out = (2.0 / hidden_dim) ** 0.5
    return {
        "gate": jax.random.normal(k1, (model_dim, num_experts),
                                  jnp.float32) * 0.02,
        "w_in": jax.random.normal(
            k2, (num_experts, model_dim, hidden_dim), dtype) * scale_in,
        "b_in": jnp.zeros((num_experts, hidden_dim), dtype),
        "w_out": jax.random.normal(
            k3, (num_experts, hidden_dim, model_dim), dtype) * scale_out,
        "b_out": jnp.zeros((num_experts, model_dim), dtype),
    }


# ---------------------------------------------------------------------------
# Top-k routing over a wide router, the held experts' part, no drop
# ---------------------------------------------------------------------------


def topk_route(h, router_w, router_bias, *, top_k: int, scale: float,
               normalize: bool = True, sum_eps: float = 0.0,
               score: str = "sigmoid"):
    """``(chosen, gates)`` of tokens ``h`` (T, D): the scores are
    ``sigmoid(h W_r)`` or, where the layout's ``score`` is ``"softmax"``,
    the softmax of ``h W_r`` over all the router's experts, in float32 at
    the highest precision (a choice hangs on them), ``chosen`` (T, k) the
    ``top_k`` experts by score plus ``router_bias`` (E,), ``gates`` (T, k)
    ``scale`` times the chosen scores, over their sum (plus ``sum_eps``,
    where a layout writes one: ``lfm2_moe``'s 1e-6) if ``normalize``.  The
    gradient runs through the scores and not through the choice or the
    bias."""
    import jax
    import jax.numpy as jnp

    if score not in SCORES:
        raise ValueError(f"score {score!r}: want one of {sorted(SCORES)}")
    logits = jnp.einsum(
        "td,de->te", h.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)
    scores = (jax.nn.sigmoid(logits) if score == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    _, chosen = jax.lax.top_k(
        jax.lax.stop_gradient(scores) + router_bias, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=1)
    if normalize:
        total = jnp.sum(picked, axis=-1, keepdims=True)
        # no "+ 0.0" where no epsilon is written: that caller's compiled
        # step stays the one it was
        picked = picked / (total + sum_eps if sum_eps else total)
    return chosen, scale * picked


def grouped_runs_fused(rows: int, k: int, n: int, dtype, *,
                       overflow: bool = False) -> bool:
    """Whether a grouped product of ``rows`` sorted rows (rows, ``k``) by
    held weights (H, ``k``, ``n``), operands in ``dtype``, runs on the
    kernels of ``grouped_pallas`` (else as ``jax.lax.ragged_dot``):

    - ``models/kernels.py::runs_fused`` of ``grouped_pallas.fits`` (``k``
      and ``n`` whole rows of 128 lanes, the rows whole row tiles: the
      published 2,048 by 1,536 and by 1,792 over 12,288 and 24,576 rows do;
      ``Config.tiny()``'s do not);
    - the form is not the ``overflow`` one (the tight and the prefix form
      are the same to this rule): a layer whose live slots pass
      :func:`prefix_rows` takes all the slots, which happens in none of
      ``lfm2_8b_a1b_packed_8k``'s steps and on one seed in nine of
      ``glm47_flash_packed_8k``'s, over a buffer three quarters live, where
      a walk over the live tiles saves least; its kernels were half of a
      step's, 44 MB of its code and 0.4 s of every start's load and
      lowering.

    The same for a product and its two gradients (``k`` and ``n`` change
    places), so a form of the routed part runs all twelve of its products
    one way."""
    from tensorflowonspark_tpu.parallel import grouped_pallas

    return runs_fused(grouped_pallas, rows, k, n, dtype, when=not overflow)


def routed_forms(slots: int, n_held: int, n_experts: int, k: int, n: int,
                 dtype) -> tuple:
    """``(rows, on the kernels)`` of every form :func:`routed_experts`
    traces for ``slots`` slots and experts (``k``, ``n``), rising by rows
    (:func:`row_sizes`): a form past :func:`prefix_rows` is the overflow
    one and runs as ``ragged_dot``, the others run where
    :func:`grouped_runs_fused` says."""
    prefix = prefix_rows(slots, n_held, n_experts)
    return tuple(
        (rows, grouped_runs_fused(rows, k, n, dtype, overflow=rows > prefix))
        for rows in row_sizes(slots, n_held, n_experts))


def grouped_step_counters(tokens: int, routing: "Routing", d: int, f: int,
                          dtype) -> dict:
    """What one step of a model of such layers adds to the program's
    counters (``kernels.step_counters``): one step of grouped products on
    the kernels or as ``jax.lax.ragged_dot``, the other named with 0 so that
    both are on the record.  The form that counts is the smallest of
    :func:`routed_forms`, the one :func:`routed_experts` takes for
    ``tokens`` tokens of width ``d`` and experts ``f`` wide when a layer's
    slots fit :func:`tight_rows`: on the kernels means that it runs all its
    products there (``moe_tight_layers_total`` and
    ``moe_overflow_layers_total`` say how often a layer took another)."""
    forms = routed_forms(tokens * routing.top_k, len(routing.held),
                         routing.n_experts, d, f, dtype)
    return step_counters("moe_grouped", forms[0][1])


def prefix_rows(slots: int, n_held: int, n_experts: int) -> int:
    """Rows the routed part of :func:`routed_experts` works on when a
    step's held slots fit them and not :func:`tight_rows` (the middle of
    :func:`row_sizes`' three): three times what an even router sends to
    ``n_held`` of ``n_experts`` experts out of ``slots`` slots, in whole
    sublanes of 8, and never more than ``slots`` (where a third or more of
    the experts are held this is all the slots, the larger of two forms
    and on the kernels).  Three, because a router
    of seeded weights sends one expert half the tokens: a layer that holds
    it lands a little over twice the even share in most steps, and at twice
    such a step took the whole form and 11 ms more, where the rows between
    twice and three times cost every step 5 (``glm47_flash_packed_8k``,
    PERF.md section 6)."""
    share = -(-3 * slots * n_held // n_experts)
    return min(slots, -(-share // 8) * 8)


def tight_rows(slots: int, n_held: int, n_experts: int) -> int:
    """Rows the routed part of :func:`routed_experts` works on when a
    step's held slots fit them: one and a half times what an even router
    sends to ``n_held`` of ``n_experts`` experts out of ``slots`` slots, in
    whole row tiles of the grouped kernels (``grouped_pallas.ROW_TILE``:
    where :func:`prefix_rows` fits the kernels, this does), and never more
    than :func:`prefix_rows`.  One and a half, for every model: a router
    near even lands 1.05 to 1.09 times the even share over a step's layers
    and a single layer more (at one and a quarter 2 to 11% of the
    layer-steps of ``lfm2_8b_a1b_packed_8k`` and ``mellum2_packed_8k``
    did not fit, on three seeds of eight), every buffer row costs a
    layer-step 0.12-0.32 us in gathers, masks, casts and the pass between
    the products whether it is live or not, and a router that is not even
    takes the next size, a layer and a step at a time (PERF.md section 6,
    PR 50)."""
    from tensorflowonspark_tpu.parallel.grouped_pallas import ROW_TILE

    share = -(-3 * slots * n_held // (2 * n_experts))
    return min(prefix_rows(slots, n_held, n_experts),
               -(-share // ROW_TILE) * ROW_TILE)


def row_sizes(slots: int, n_held: int, n_experts: int) -> tuple:
    """The distinct row counts the routed part is traced at, rising:
    :func:`tight_rows`, :func:`prefix_rows` and all the ``slots`` (the
    overflow form).  Sizes that coincide are one form: where a third or
    more of the experts are held :func:`prefix_rows` is all the slots, and
    where a tile is more than :func:`prefix_rows` the tight size is it."""
    return tuple(sorted({tight_rows(slots, n_held, n_experts),
                         prefix_rows(slots, n_held, n_experts), slots}))


@functools.lru_cache(maxsize=None)
def _routed_part(scopes: tuple = ()):
    """The held experts' part of the layer after the router, as a function
    of how many sorted rows it works on.  Made once for the calls that sit
    under the same ``scopes`` (the module imports JAX only when it is
    used)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    f32 = jnp.float32

    def no_grad(a):
        return np.zeros(a.shape, jax.dtypes.float0)

    def rows_of_choice(rows, inv, k, j):
        """(R, D) sorted rows -> (T, D) float32: the row of every token's
        ``j``-th choice, zero where its place is past R.  A choice at a
        time: (T k, D) reshaped to (T, k, D) is a copy on a TPU (k rows to
        a tile of 8), and no float32 array has a row a slot."""
        return jnp.take(rows, inv.reshape(-1, k)[:, j], axis=0, mode="fill",
                        fill_value=0).astype(f32)

    # The two row permutations.  A gather's own transpose is a scatter,
    # which a TPU runs a row at a time (0.87 ms for 8,192 rows of 2,048
    # where a gather of as many takes 0.05): each has gathers as its
    # backward pass.  ``idx`` (R,) are the first R slots in sorted order (slot
    # ``t k + j`` is token ``t``'s ``j``-th choice), ``inv`` (T k,) every
    # slot's place in that order.

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def spread(x, idx, inv, k):
        """(T, D) tokens -> (R, D): row ``i`` is the token of slot
        ``idx[i]``."""
        return jnp.take(x, idx // k, axis=0)

    def spread_fwd(x, idx, inv, k):
        return spread(x, idx, inv, k), (idx, inv)

    def spread_bwd(k, saved, d):
        idx, inv = saved
        with jax.named_scope("moe_dispatch"):
            dx = sum(rows_of_choice(d, inv, k, j) for j in range(k))
        return dx.astype(d.dtype), no_grad(idx), no_grad(inv)

    spread.defvjp(spread_fwd, spread_bwd)

    @jax.custom_vjp
    def combine(rows, gates, idx, inv):
        """Sorted rows (R, D) -> (T, D): ``y[t] = sum over j of
        gates[t, j] rows[inv[t k + j]]``, summed in float32."""
        k = gates.shape[1]
        return sum(gates[:, j, None] * rows_of_choice(rows, inv, k, j)
                   for j in range(k)).astype(rows.dtype)

    def combine_fwd(rows, gates, idx, inv):
        return combine(rows, gates, idx, inv), (rows, gates, idx, inv)

    def combine_bwd(saved, dy):
        rows, gates, idx, inv = saved
        with jax.named_scope("moe_combine"):
            dy_rows = jnp.take(dy, idx // gates.shape[1], axis=0).astype(f32)
            d_rows = jnp.take(gates.reshape(-1), idx)[:, None] * dy_rows
            d_gates = jnp.take(jnp.sum(dy_rows * rows.astype(f32), axis=-1),
                               inv, mode="fill", fill_value=0)
        return (d_rows.astype(rows.dtype), d_gates.reshape(gates.shape),
                no_grad(idx), no_grad(inv))

    combine.defvjp(combine_fwd, combine_bwd)

    def over_rows(n_rows, fused, k, order, inv, group_sizes, x, gates, w_gate,
                  w_up, w_down):
        """The routed part over the first ``n_rows`` sorted slots, which
        hold every live one: spread, three grouped products (``fused``: on
        the kernels, as :func:`grouped_runs_fused` said), weighted sum into
        (T, D)."""
        dtype = x.dtype
        with jax.named_scope("moe_dispatch"):
            idx = order[:n_rows]
            live = (jnp.arange(n_rows) < jnp.sum(group_sizes))[:, None]
            # rows past the live ones belong to no group: whatever a
            # grouped product leaves there never meets a live row
            xs = jnp.where(live, spread(x, idx, inv, k), 0)

        if fused:
            from tensorflowonspark_tpu.parallel import grouped_pallas

            with jax.named_scope("moe_experts"):
                visits = grouped_pallas.plan(group_sizes, n_rows)

        def grouped(rows, w):
            with jax.named_scope("moe_experts"):
                if fused:
                    return grouped_pallas.grouped_product(rows, w, visits,
                                                          "moe_experts")
                return jax.lax.ragged_dot(rows, w.astype(dtype), group_sizes,
                                          preferred_element_type=f32)

        act = (jax.nn.silu(grouped(xs, w_gate))
               * grouped(xs, w_up)).astype(dtype)
        out = grouped(act, w_down)
        with jax.named_scope("moe_combine"):
            out = jnp.where(live, out, 0).astype(dtype)
            return combine(out, gates, idx, inv)

    def by_count(sizes, fused, form, order, inv, group_sizes, *operands):
        """``form(n_rows, fused)`` of the operands at the smallest of the
        rising ``sizes`` that holds this step's live slots (the last is
        all the slots: the overflow form); the device chooses.  ``fused``
        says of each size whether its products run on the kernels."""
        rung = jnp.sum(jnp.sum(group_sizes)
                       > jnp.asarray(sizes[:-1], group_sizes.dtype))
        return jax.lax.switch(rung, [form(*at) for at in zip(sizes, fused)],
                              order, inv, group_sizes, *operands)

    # A differentiated ``switch`` has every branch write zeros in the
    # place of the others' residuals: the forward and the backward pass
    # choose each for itself, and the backward one makes the products again.

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
    def routed(sizes, fused, k, *args):
        return by_count(sizes, fused, lambda n, on_kernels:
                        functools.partial(over_rows, n, on_kernels, k), *args)

    def routed_fwd(sizes, fused, k, *args):
        return routed(sizes, fused, k, *args), args

    def routed_bwd(sizes, fused, k, args, dy):
        def backward(n_rows, on_kernels):
            def run(order, inv, group_sizes, dy, *operands):
                return jax.vjp(functools.partial(
                    over_rows, n_rows, on_kernels, k, order, inv,
                    group_sizes), *operands)[1](dy)
            return run

        grads = by_count(sizes, fused, backward, *args[:3], dy, *args[3:])
        return (*map(no_grad, args[:3]), *grads)

    routed.defvjp(routed_fwd, routed_bwd)
    # A model's expert layers share their shapes: under ``jax.jit`` the part
    # is traced, differentiated and lowered once a shape and an execution
    # (both are in the static arguments), not once a layer.  The trace of a
    # step is paid at every start, warm or cold (PERF.md section 6, PR 42).
    # The compiler names every copy of a shared function after all its
    # callers, so calls under different ``jax.named_scope``s (a prediction
    # module's layer) get a function of their own: ``scopes``.
    return jax.jit(routed, static_argnums=(0, 1, 2))


def routed_experts(x, router_w, router_bias, w_gate, w_up, w_down, held, *,
                   top_k: int, scale: float, normalize: bool = True,
                   sum_eps: float = 0.0, score: str = "sigmoid",
                   scopes: tuple = ()):
    """The held experts' part of a routed SwiGLU layer on tokens ``x``
    (T, D): ``sum over e chosen and held of g_e W_down_e (silu(x W_gate_e)
    * (x W_up_e))``, and the tokens that chose each of the router's experts.

    ``router_w`` (D, E) and ``router_bias`` (E,) span all ``E`` experts;
    ``w_gate``, ``w_up`` (H, D, F) and ``w_down`` (H, F, D) are the ``H``
    experts held here, ``held`` (a static sequence of ``H`` distinct ids in
    ``[0, E)``) says which they are, in the weights' order.  Products take
    operands in ``x``'s type and accumulate in float32; routing is float32
    (:func:`topk_route`, which ``top_k``, ``scale``, ``normalize``,
    ``sum_eps`` and ``score`` go to).  ``scopes``
    are the ``jax.named_scope``s the caller has opened round the layer, where
    a profile should tell its operations from another layer's (``mla_moe``'s
    prediction module): layers that name the same share one traced part.

    Every slot is kept: the ``T top_k`` slots are sorted by held expert
    (those of experts held elsewhere last), so the live ones are the first
    ``sum(counts[held])`` rows, and everything after the router runs over
    :func:`tight_rows` of them where the step's live rows fit, over
    :func:`prefix_rows` where they fit those, over all the slots where they
    do not (one function at three sizes, :func:`row_sizes`; the device
    chooses, a layer and a step at a time).  Returns ``(y, counts)``: ``y``
    (T, D) in ``x``'s type, ``counts`` (E,) int32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n_experts, n_held = router_w.shape[1], len(held)
    held = np.asarray(held, np.int32)
    if (n_held != w_gate.shape[0] or len(set(held.tolist())) != n_held
            or held.min(initial=0) < 0 or held.max(initial=0) >= n_experts):
        raise ValueError(f"held {held.tolist()}: want {w_gate.shape[0]} "
                         f"distinct experts of {n_experts}")
    with jax.named_scope("moe_router"):
        chosen, gates = topk_route(x, router_w, router_bias, top_k=top_k,
                                   scale=scale, normalize=normalize,
                                   sum_eps=sum_eps, score=score)
        slot_expert = chosen.reshape(-1)
        counts = jnp.sum(slot_expert[:, None] == jnp.arange(n_experts),
                         axis=0, dtype=jnp.int32)
    with jax.named_scope("moe_dispatch"):
        place = np.full(n_experts, n_held, np.int32)    # elsewhere: last
        place[held] = np.arange(n_held)
        order = jnp.argsort(jnp.asarray(place)[slot_expert], stable=True)
        inv = jnp.argsort(order)
    n_slots = slot_expert.shape[0]
    sizes, fused = zip(*routed_forms(n_slots, n_held, n_experts,
                                     *w_gate.shape[1:], x.dtype))
    y = _routed_part(tuple(scopes))(sizes, fused, top_k, order, inv,
                                    counts[held], x, gates, w_gate, w_up,
                                    w_down)
    return y, counts


class Routing(NamedTuple):
    """A layout's routed layers under one set of names (every published
    configuration names them its own way; a model's ``routing(config)``
    translates once, for :func:`expert_ffn` and for the skeleton that steps
    and reads the routing state, ``models/packed_decoder.py``)."""
    n_experts: int          # the router's width
    layers: int             # layers with a router: the routing state's rows
    held: tuple             # the experts this chip holds, the weights' order
    top_k: int
    scale: float
    normalize: bool
    speed: float            # the correction bias's step (0.0: it stays)
    sum_eps: float = 0.0    # :func:`topk_route`'s
    score: str = "sigmoid"  # the layout's, of :data:`SCORES`


def expert_ffn(params, prefix: str, h, bias, routing: Routing, *,
               shared: bool = False, scopes: tuple = ()):
    """An expert layer's feed-forward on tokens ``h`` (N, D), from a flat
    parameter dict: :func:`routed_experts` of ``router`` and
    ``experts_{gate,up,down}`` under ``prefix`` and, where the layout has
    one (``shared``), the shared expert's SwiGLU (``shared_{gate,up,down}``,
    under the ``jax.named_scope`` ``shared_expert``) added to it.  ``bias``
    (E,) is the layer's correction bias, ``scopes`` the named scopes the
    layer sits under.  Returns ``(y, counts)``, ``counts`` (E,) the tokens
    that chose each of the router's experts."""
    import jax

    from tensorflowonspark_tpu.models.packed_rows import swiglu

    if shared:
        with jax.named_scope("shared_expert"):
            y = swiglu(h, params[prefix + "shared_gate"],
                       params[prefix + "shared_up"],
                       params[prefix + "shared_down"])
    routed, counts = routed_experts(
        h, params[prefix + "router"], bias, params[prefix + "experts_gate"],
        params[prefix + "experts_up"], params[prefix + "experts_down"],
        routing.held, top_k=routing.top_k, scale=routing.scale,
        normalize=routing.normalize, sum_eps=routing.sum_eps,
        score=routing.score, scopes=scopes)
    return (y + routed if shared else routed), counts


# ---------------------------------------------------------------------------
# The routing state of a model of such layers: what takes no gradient
# ---------------------------------------------------------------------------

#: the rows of the routing state that only :func:`routing_counters` reads
#: (no step's computation does): a checkpoint written before one of them
#: was counted restores with it at zero (``Trainer.restore``)
COUNTER_ROWS = ("counts", "busiest", "overflow", "tight")


def routing_state_shapes(n_experts: int, expert_layers: int) -> dict:
    """Name -> ``(shape, dtype)`` of a model's routing state, a row an
    expert layer in forward order: every layer's correction bias (it enters
    the choice and takes no gradient), the cumulative count of tokens by
    expert, the cumulative size of the layer's fullest expert, the steps
    in which the layer's held slots overflowed :func:`prefix_rows` and
    those in which they fitted :func:`tight_rows`.
    The Trainer's stateful step threads and checkpoints the collection."""
    rows, e = expert_layers, n_experts
    return {"bias": ((rows, e), "float32"), "counts": ((rows, e), "int32"),
            "busiest": ((rows,), "int32"), "overflow": ((rows,), "int32"),
            "tight": ((rows,), "int32")}


def step_routing_state(state: dict, counts, held, *, top_k: int,
                       speed: float, tokens: int) -> dict:
    """The routing state after a step whose ``tokens`` tokens chose
    ``counts`` (expert layers, E): every layer's bias moves ``speed``
    towards its mean load (``b_e += speed * sign(mean(c) - c_e)``,
    arXiv:2412.19437), the counts add up, and a layer is counted whose
    held experts were chosen more often than :func:`prefix_rows` allows
    (:func:`routed_experts` then took all the slots), or no more often
    than :func:`tight_rows` does (it took the smallest size)."""
    import jax.numpy as jnp

    load = counts.astype(jnp.float32)
    held = jnp.asarray(held, jnp.int32)
    shape = tokens * top_k, len(held), counts.shape[-1]
    landed = jnp.sum(counts[:, held], axis=-1)
    return {
        "bias": state["bias"] + speed * jnp.sign(
            jnp.mean(load, axis=-1, keepdims=True) - load),
        "counts": state["counts"] + counts,
        "busiest": state["busiest"] + jnp.max(counts, axis=-1),
        "overflow": state["overflow"] + (landed > prefix_rows(*shape)),
        "tight": state["tight"] + (landed <= tight_rows(*shape)),
    }


def routing_counters(state: dict, held) -> dict:
    """What the device decided, for the program's counters (a model's
    ``device_counters`` hook): cumulative int32 arrays whose growth the
    Trainer adds up, element by element (a running total would outgrow 32
    bits; an element takes a million steps of a row to).  Slots (a token's
    choice of an expert) routed, the slots whose expert is held here, every
    layer's fullest expert, the layers whose held slots overflowed
    :func:`prefix_rows` and those whose held slots fitted
    :func:`tight_rows`.  One set of names for every model of such layers:
    one reader serves them all."""
    import jax.numpy as jnp

    held = jnp.asarray(held, jnp.int32)
    return {"moe_slots_total": state["counts"],
            "moe_local_slots_total": state["counts"][:, held],
            "moe_busiest_expert_slots_total": state["busiest"],
            "moe_overflow_layers_total": state["overflow"],
            "moe_tight_layers_total": state["tight"]}
