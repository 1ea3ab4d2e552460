"""Sharded training-step factory: one code path for every strategy.

Reference anchor: the reference exposes three distinct training strategies —
between-graph DP (``TFNode.py::start_cluster_server`` + replica device
setter), collective DP (``MultiWorkerMirroredStrategy`` built from the
``TF_CONFIG`` that ``TFSparkNode.py::_mapfn`` writes), and parameter servers
(``num_ps`` of ``TFCluster.py::run``).  On TPU all three collapse into one
``jax.jit`` over a mesh (``SURVEY.md §2.3``):

- DP/MWMS   → batch sharded over ``dp``; XLA inserts the grad ``psum``.
- ``num_ps``→ there are no parameter servers on a TPU pod; the same capacity
  concern (don't replicate optimizer state everywhere) maps to ZeRO-style
  sharding of params/optimizer state over the ``fsdp`` axis
  (``reduce_scatter``/``all_gather`` emitted by XLA from the shardings).
- TP/SP     → extra mesh axes, free through the same jit.

The factory returns a step that is compiled ONCE (static shapes, no Python
control flow inside) and donates the state buffers so params update in-place
in HBM.

On data-parallel-only meshes the gradient exchange is no longer left to
GSPMD: :func:`make_train_step` dispatches to the bucketed, overlapped
collective step (``parallel/collectives.py`` — explicit per-bucket ``psum``
issued as backward produces gradients) unless ``TFOS_BUCKETED_ALLREDUCE=0``
or the mesh/model combination requires the monolithic path.
"""

from __future__ import annotations

import logging
import os
from typing import Any, Callable

from tensorflowonspark_tpu.parallel import mesh as mesh_lib

logger = logging.getLogger(__name__)

#: default ZeRO / sharded-update size floor in BYTES — equal to the
#: historical ``1 << 16``-*element* threshold for f32 params, so the
#: default behaviour is unchanged where it was tuned
DEFAULT_ZERO_MIN_BYTES = 1 << 18


def zero_min_bytes() -> int:
    """Size floor (bytes) below which a leaf is not worth sharding —
    ``TFOS_ZERO_MIN_BYTES`` override, else :data:`DEFAULT_ZERO_MIN_BYTES`.

    One knob for two boundaries that must agree: ``apply_zero_sharding``'s
    don't-bother threshold and the sharded-update scatter eligibility
    (``shapes.update_shard_eligible``).  If they diverged, a leaf could be
    ZeRO-sharded yet ride the replicated gradient path (memory saved, comm
    win lost) or vice versa (a degenerate one-leaf scatter bucket for a
    leaf whose optimizer state nobody bothered to shard)."""
    env = os.environ.get("TFOS_ZERO_MIN_BYTES", "")
    try:
        return max(1, int(env)) if env else DEFAULT_ZERO_MIN_BYTES
    except ValueError:
        return DEFAULT_ZERO_MIN_BYTES


def path_keys(path) -> tuple:
    """Normalize a jax keypath to a tuple of plain strings — the matching
    key for "optimizer-state leaf belongs to param" lookups
    (:func:`state_shardings` and the sharded-update in-region specs,
    ``parallel/collectives.py``)."""
    return tuple(
        str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k))))
        for k in path
    )


def unbox(tree):
    """Strip flax ``Partitioned`` metadata boxes, if any."""
    try:
        import flax.linen as nn

        return nn.meta.unbox(tree)
    except Exception:
        return tree


class TrainState:
    """Minimal pytree train state: ``params``, ``opt_state``, ``step``, plus
    optional non-param variable ``collections`` (e.g. BatchNorm
    ``batch_stats`` — running mean/var updated inside the step but not by
    the optimizer).

    A hand-rolled pytree (not flax's TrainState) so the apply/optimizer
    functions stay out of the leaves — they'd otherwise be retraced into
    every jit signature and break donation.
    """

    def __init__(self, params, opt_state, step, collections=None):
        self.params = params
        self.opt_state = opt_state
        self.step = step
        self.collections = collections if collections is not None else {}

    def tree_flatten(self):
        return (self.params, self.opt_state, self.step, self.collections), None

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(*children)


import jax.tree_util as _jtu  # noqa: E402

_jtu.register_pytree_node_class(TrainState)


def create_train_state(params, optimizer, collections=None):
    import jax.numpy as jnp

    params = unbox(params)
    return TrainState(params, optimizer.init(params), jnp.zeros((), jnp.int32),
                      unbox(collections) if collections else {})


def merge_collection_shardings(collections, mesh, overrides=None):
    """Per-collection shardings: a model-prescribed override wins, every
    other collection replicates.  The one merge used by init
    (``Trainer.__init__``), train (``state_shardings``), and eval
    (``make_eval_step``) compilation, so the three can't diverge."""
    import jax

    overrides = overrides or {}
    return {
        name: (overrides[name] if name in overrides
               else jax.tree_util.tree_map(
                   lambda _: mesh_lib.replicated(mesh), tree))
        for name, tree in (collections or {}).items()
    }


def state_shardings(state: TrainState, param_shardings, mesh,
                    collection_shardings=None, opt_param_shardings=None):
    """Shardings for the full train state.

    Optimizer-state leaves carry the sharding the eager ``optimizer.init``
    already propagated from the (committed, sharded) params — param-shaped
    leaves (Adam ``mu``/``nu``) therefore inherit exactly their param's
    layout, including ZeRO ``fsdp`` sharding (the ``num_ps`` mapping).
    Leaves without a mesh sharding (step counts, EMA decay scalars)
    replicate.

    ``opt_param_shardings`` optionally substitutes a DIFFERENT param-tree
    of shardings for that optimizer-state inheritance only (params keep
    ``param_shardings``) — the sharded-update step stores each
    scatter-eligible param's ``mu``/``nu`` as the dim-0 slice its
    ``psum_scatter`` block lands on (``P((data_axes...), ...)``), so the
    scattered gradient shard and the optimizer state meet on-device with
    no resharding hop (``parallel/collectives.py``).

    ``collection_shardings`` optionally maps a collection name to a pytree
    of shardings for its leaves (e.g. wide&deep's embedding tables sharded
    over the vocab dim — the module hook ``make_collection_shardings``);
    unnamed collections replicate as before.
    """
    import jax

    _norm = path_keys

    # param tree path -> (shape, sharding): optax state trees (Adam mu/nu,
    # momentum, …) embed the SAME sub-tree structure as params, so an opt
    # leaf's path ends with its param's path
    flat_params = jax.tree_util.tree_flatten_with_path(state.params)[0]
    flat_shards = jax.tree_util.tree_leaves(
        opt_param_shardings if opt_param_shardings is not None
        else param_shardings,
        is_leaf=lambda x: hasattr(x, "spec")
    )
    by_path = {
        _norm(path): (getattr(leaf, "shape", ()), shard)
        for (path, leaf), shard in zip(flat_params, flat_shards)
    }

    degraded = []

    def _opt_leaf(path, leaf):
        shape = getattr(leaf, "shape", ())
        norm = _norm(path)
        for i in range(len(norm)):  # longest param-path suffix wins
            hit = by_path.get(norm[i:])
            if hit and hit[0] == shape:
                return hit[1]
        s = getattr(leaf, "sharding", None)
        if isinstance(s, jax.sharding.NamedSharding) and s.mesh == mesh:
            return s
        if getattr(leaf, "ndim", 0) > 0 and getattr(leaf, "size", 0) > 1:
            degraded.append(shape)
        return mesh_lib.replicated(mesh)

    opt_shardings = jax.tree_util.tree_map_with_path(_opt_leaf, state.opt_state)
    if degraded:
        logger.warning(
            "%d non-scalar optimizer-state leaves match no param by tree "
            "path and carry no mesh sharding; they will be REPLICATED "
            "(ZeRO memory savings lost for them); shapes: %s",
            len(degraded), degraded[:5],
        )
    # non-param collections (batch_stats running averages) replicate unless
    # the model prescribed a sharding for them: their batch-dim reductions
    # are global under pjit view, so every device holds the same values
    col_shardings = merge_collection_shardings(
        state.collections, mesh, collection_shardings)
    return TrainState(param_shardings, opt_shardings,
                      mesh_lib.replicated(mesh), col_shardings)


def apply_zero_sharding(param_shardings, mesh, params,
                        min_size: int | None = None):
    """Extend param shardings with an ``fsdp`` dimension (ZeRO / num_ps map).

    For each parameter at least :func:`zero_min_bytes` big (the
    ``TFOS_ZERO_MIN_BYTES`` knob, shared with the sharded-update scatter
    eligibility so the two boundaries cannot drift), shard its largest
    not-yet-sharded, fsdp-divisible dimension over ``fsdp``.  An explicit
    ``min_size`` keeps the historical ELEMENT-count semantics (tests pin
    ``min_size=1`` to shard everything).
    """
    import jax

    fsdp = mesh.shape["fsdp"]
    if fsdp <= 1:
        return param_shardings
    min_bytes = zero_min_bytes() if min_size is None else None

    def _one(sharding, leaf):
        shape = getattr(leaf, "shape", ())
        spec = list(sharding.spec) + [None] * (len(shape) - len(sharding.spec))
        size = getattr(leaf, "size", 0)
        if min_bytes is not None:
            itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", 4)
            if size * itemsize < min_bytes:
                return sharding
        elif size < min_size:
            return sharding
        dims = sorted(range(len(shape)), key=lambda d: -shape[d])
        for d in dims:
            if spec[d] is None and shape[d] % fsdp == 0:
                spec[d] = "fsdp"
                return mesh_lib.named_sharding(mesh, *spec)
        return sharding

    return jax.tree_util.tree_map(
        _one, param_shardings, params, is_leaf=lambda x: hasattr(x, "spec")
    )


class _MeshBoundFn:
    """A jitted fn that traces/runs with its mesh entered as the active mesh
    (``mesh_lib.active_mesh``), so model code can place mesh-aware sharding
    constraints (e.g. ``models._common.embedding_lookup``).  Forwards
    ``lower``/attribute access to the underlying jitted callable so AOT
    compilation (``bench.py``) keeps working.
    """

    def __init__(self, jitted, mesh):
        self._jitted = jitted
        self._mesh = mesh

    def __call__(self, *args, **kwargs):
        with mesh_lib.active_mesh(self._mesh):
            return self._jitted(*args, **kwargs)

    def lower(self, *args, **kwargs):
        with mesh_lib.active_mesh(self._mesh):
            return self._jitted.lower(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._jitted, name)


def compile_step(
    step_fn: Callable[[TrainState, Any], Any],
    mesh,
    param_shardings,
    state: TrainState,
    batch_example: Any,
    sequence_axes: dict[str, int] | None = None,
    donate: bool = True,
    collection_shardings=None,
    opt_param_shardings=None,
):
    """Jit an arbitrary ``state, batch -> state, loss`` step over the mesh.

    Computes the full train-state shardings (params + optimizer state +
    collections) and batch shardings, jits with buffer donation, and binds
    the mesh as the active mesh at trace/run time (:class:`_MeshBoundFn`).
    This is the shared lower half of :func:`make_train_step`; model-zoo
    modules with a custom step (e.g. wide&deep's sparse embedding update,
    ``models/widedeep.py::make_sharded_train_step``) call it directly.
    ``opt_param_shardings`` is threaded to :func:`state_shardings` (the
    sharded-update step's scatter-sliced optimizer-state storage).
    """
    import jax

    shardings = state_shardings(state, param_shardings, mesh,
                                collection_shardings=collection_shardings,
                                opt_param_shardings=opt_param_shardings)
    batch_shardings = _batch_shardings(mesh, batch_example, sequence_axes)

    return _MeshBoundFn(
        jax.jit(
            step_fn,
            in_shardings=(shardings, batch_shardings),
            out_shardings=(shardings, mesh_lib.replicated(mesh)),
            donate_argnums=(0,) if donate else (),
        ),
        mesh,
    )


def _batch_shardings(mesh, batch_example, sequence_axes=None):
    """Per-leaf batch shardings: axis 0 over (dp, fsdp), named sequence
    axes over sp (one rule for the train and eval compile paths)."""
    import jax

    def _one(leaf_path, leaf):
        name = leaf_path[-1].key if leaf_path and hasattr(leaf_path[-1], "key") else None
        sa = (sequence_axes or {}).get(name)
        return mesh_lib.batch_sharding(mesh, getattr(leaf, "ndim", 0), sa)

    return jax.tree_util.tree_map_with_path(_one, batch_example)


#: ``jax.named_scope`` names round the step's phases.  They are metadata of
#: the compiled program (its operations, and the compile-cache key, which
#: leaves metadata out, stay what they were): a profile's device
#: operations then carry ``.../forward/...``, ``.../transpose(jvp(forward))/
#: ...`` (JAX's own name for the backward of a scope) or ``.../optimizer/...``
#: in their names, and group by phase.
FORWARD_SCOPE = "forward"
OPTIMIZER_SCOPE = "optimizer"


def _forward_scoped(loss_fn):
    """``loss_fn`` under the ``forward`` scope (its attributes kept)."""
    import functools

    import jax

    @functools.wraps(loss_fn)
    def scoped(*args):
        with jax.named_scope(FORWARD_SCOPE):
            return loss_fn(*args)

    return scoped


def make_train_step(
    loss_fn: Callable[[Any, Any], Any],
    optimizer,
    mesh,
    param_shardings,
    state: TrainState,
    batch_example: Any,
    sequence_axes: dict[str, int] | None = None,
    donate: bool = True,
    collection_shardings=None,
    bucketed: bool | None = None,
    mesh_config=None,
    clip_global_norm: float | None = None,
):
    """Compile ``state, batch -> state, loss`` over the mesh.

    ``loss_fn(params, batch) -> scalar loss`` must be pure and
    trace-compatible (static shapes; ``lax`` control flow only —
    XLA semantics per the TPU design notes).  A *stateful* loss
    (``loss_fn.stateful`` truthy, signature
    ``loss_fn(params, collections, batch) -> (loss, new_collections)``)
    additionally threads non-param variable collections — the BatchNorm
    path; running stats update inside the same compiled step.

    ``bucketed`` selects the gradient-exchange structure:

    - ``None`` (default): the bucketed, overlapped collective step
      (``parallel/collectives.py``) when ``TFOS_BUCKETED_ALLREDUCE`` is on
      (default) and the mesh is data-parallel-only
      (``collectives.mesh_eligibility``); otherwise the monolithic GSPMD
      step below.
    - ``True``: force the bucketed step (raises with the reason when the
      mesh/model combination cannot support it) — the bench A/B path.
    - ``False``: force the monolithic step.

    ``mesh_config`` (the :class:`mesh.MeshConfig` the mesh was built from,
    when the caller has it) lets the bucketed step stage its collectives
    per interconnect tier on multi-slice topologies — the ``Mesh`` object
    itself does not record how its axes map onto ICI vs DCN.

    ``clip_global_norm`` clips gradients to that global norm before the
    optimizer update (``optax.clip_by_global_norm`` semantics) on EVERY
    step structure, including the sharded-update bucketed step — where
    the norm is computed as sharded partials combined by reduce-scatter
    + all-gather, so clipped optimizers no longer need
    ``TFOS_SHARDED_UPDATE=0``.  Prefer this over wrapping ``optimizer``
    in ``optax.chain(optax.clip_by_global_norm(...), ...)``: the chain
    changes the opt-state structure and silently computes shard-local
    norms on the sharded path.

    The returned step always carries ``.bucketed`` so callers (trainer
    flight attribution, bench) can see which structure compiled.
    """
    import jax

    from tensorflowonspark_tpu.parallel import collectives

    stateful = bool(getattr(loss_fn, "stateful", False))
    if getattr(loss_fn, "tables_frozen", False):
        logger.warning(
            "loss_fn marks its embedding tables as collection-resident "
            "(tables_frozen): the generic optax step will train only the "
            "dense params and leave the tables at their initial values. "
            "Use the model's make_sharded_train_step (the Trainer picks it "
            "up automatically) to train the tables."
        )

    loss_fn = _forward_scoped(loss_fn)
    if bucketed is not False:
        ok, reason = collectives.mesh_eligibility(mesh, collection_shardings)
        if bucketed is None and not collectives.bucketing_enabled():
            ok, reason = False, "TFOS_BUCKETED_ALLREDUCE=0"
        if ok:
            return collectives.make_bucketed_train_step(
                loss_fn, optimizer, mesh, param_shardings, state,
                batch_example, sequence_axes=sequence_axes, donate=donate,
                collection_shardings=collection_shardings,
                mesh_config=mesh_config,
                clip_global_norm=clip_global_norm)
        if bucketed:
            raise ValueError(f"bucketed train step unavailable: {reason}")
        logger.debug("monolithic train step (%s)", reason)

    def _step(st: TrainState, batch):
        if stateful:
            (loss, new_cols), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                st.params, st.collections, batch
            )
        else:
            loss, grads = jax.value_and_grad(loss_fn)(st.params, batch)
            new_cols = st.collections
        import optax

        with jax.named_scope(OPTIMIZER_SCOPE):
            if clip_global_norm is not None:
                grads, _ = optax.clip_by_global_norm(
                    float(clip_global_norm)).update(grads,
                                                    optax.EmptyState())
            updates, opt_state = optimizer.update(grads, st.opt_state,
                                                  st.params)
            params = optax.apply_updates(st.params, updates)
        return TrainState(params, opt_state, st.step + 1, new_cols), loss

    step = compile_step(_step, mesh, param_shardings, state, batch_example,
                        sequence_axes=sequence_axes, donate=donate,
                        collection_shardings=collection_shardings)
    step.bucketed = False
    step.clip_global_norm = clip_global_norm
    return step


def make_eval_step(forward_fn, mesh, param_shardings, batch_example,
                   sequence_axes: dict[str, int] | None = None,
                   collections=None, collection_shardings=None):
    """Compile a sharded ``params, batch -> outputs`` inference step.

    A stateful forward (``forward_fn.stateful`` truthy) has signature
    ``forward_fn(params, collections, batch)`` — BatchNorm running stats are
    read (not updated) at eval time.  ``collection_shardings`` mirrors
    :func:`state_shardings`' option (model-prescribed table shardings).
    """
    import jax

    batch_shardings = _batch_shardings(mesh, batch_example, sequence_axes)
    if getattr(forward_fn, "stateful", False):
        col_shardings = merge_collection_shardings(
            collections, mesh, collection_shardings)
        return _MeshBoundFn(
            jax.jit(
                forward_fn,
                in_shardings=(param_shardings, col_shardings, batch_shardings),
            ),
            mesh,
        )
    return _MeshBoundFn(
        jax.jit(
            forward_fn,
            in_shardings=(param_shardings, batch_shardings),
        ),
        mesh,
    )
