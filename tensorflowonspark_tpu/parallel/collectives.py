"""Bucketed, overlapped gradient collectives for the train-step path.

The monolithic step (``train.make_train_step``) leaves the cross-replica
gradient exchange entirely to GSPMD: one ``jax.value_and_grad`` over the
globally-sharded batch, with XLA free to place (and its combiner pass free
to fuse) the grad all-reduces wherever it likes — in practice after the
whole backward, so no gradient byte moves over ICI until the last gradient
is produced.  This module implements "Automatic Cross-Replica Sharding of
Weight Update in Data-Parallel Training" (PAPERS.md 2004.13336) on the
step path, with the bucket-size discipline both MPI characterization
studies (PAPERS.md 1603.02339, 1810.11112) measured: bucketed/overlapped
collectives dominate monolithic ones at exactly the message sizes a
model's gradient pytree produces.

Mechanism: the gradient pytree is partitioned into size-bounded **buckets**
(``TFOS_ALLREDUCE_BUCKET_MB``; leaves larger than a bucket stand alone,
small leaves coalesce in deterministic flatten order, and a bucket never
mixes dtypes — a silent f32/bf16 upcast would inflate collective bytes and
skew the analytic model below), and the step is rebuilt as a ``shard_map``
over the data axes (``dp``/``fsdp``) issuing one explicit collective per
bucket in reverse flatten order — the order backward produces gradients —
so XLA's latency-hiding scheduler overlaps bucket *i*'s exchange with the
backward still producing bucket *i-1*.

Two exchange structures compile from the same buckets:

- **sharded weight update** (default, ``TFOS_SHARDED_UPDATE``): each
  bucket's gradients are **reduce-scattered** (``psum_scatter``) so every
  replica holds only its 1/N shard, the optimizer update for that shard
  runs *inside* the manual region against optimizer state stored in the
  same dim-0-slice layout (no resharding hop — ``train.state_shardings``'
  ``opt_param_shardings``), and the updated parameter shards are
  **all-gathered** back.  Gradient-exchange bytes on backward's critical
  path halve (the parameter all-gather overlaps the next forward, the
  PR 12 overlap property), the update's FLOPs and optimizer-state memory
  drop to 1/N — the 2004.13336 core claim.  Leaves too small for the ZeRO
  threshold (``train.zero_min_bytes``, the shared
  ``TFOS_ZERO_MIN_BYTES`` knob) or whose leading dim does not divide the
  data world (``shapes.update_shard_eligible``) ride a **replicated fast
  path**: their bucket is reduce-scattered and immediately all-gathered
  (sum everywhere — same bytes as an all-reduce, same HLO op family) and
  their update is computed redundantly, exactly as before.  The loss and
  floating collection leaves ride the same scatter+gather exchange, so
  the sharded step's HLO contains **zero all-reduce ops**.
- **bucketed all-reduce** (``TFOS_SHARDED_UPDATE=0`` or
  ``update_shard=False``): the PR 12 structure — per-bucket variadic
  ``pmean``, optimizer update outside the region on full gradients.

On **multi-slice meshes** the exchange is staged per interconnect tier
when the topology allows it: an in-slice reduce-scatter over the ICI
axes, then a cross-slice stage over the DCN axis (and the all-gathers
inverted), with the bucket bound raised to the DCN tier's own sizing
(``TFOS_DCN_BUCKET_MB``, else four times the ICI bound) since every
bucket crosses both tiers and the slow tier dominates.  A named
mesh axis cannot be subdivided, so true two-tier staging requires the
DCN axis to be *purely* cross-slice (``MeshConfig.dcn_axis()`` size ==
``slices``); anything else falls back to single-tier with the reason
recorded on the step (``.tier_reason``) — XLA still decomposes the
collective across the hybrid mesh, the framework just can't stage bucket
sizes per tier.

Composition contract (everything the monolithic step supports):

- **stateful losses** (BatchNorm collections): local ``(loss, new_cols)``
  per data shard; the returned loss and every *floating* collection leaf
  are cross-replica averaged, so running statistics track the global
  batch mean exactly (batch-*mean* statistics are linear; a batch
  *variance* differs from the global-view one by the between-shard mean
  spread — the standard local-BatchNorm DDP semantics, restored to
  global-view by ``TFOS_BUCKETED_ALLREDUCE=0``).
- **ZeRO** ``fsdp`` sharding: params enter the manual region replicated
  (XLA all-gathers the ``fsdp`` shards — the same per-weight collective
  ZeRO issues anyway); under the sharded update the optimizer state is
  sharded 1/N over *all* data axes (strictly finer than ZeRO's
  fsdp-only split), under the all-reduce structure it keeps the
  inherited ZeRO layout.
- **elementwise optimizer transforms** on the sharded-update path: the
  in-region update sees each replica's 1/N parameter slice, which is
  exact for per-element transforms (Adam/AdamW/SGD/momentum — the
  ``optax`` default here).  The one global-reduction transform serving
  needs — global-norm clipping — is built in: ``clip_global_norm=``
  computes the norm as each replica's shard-local square-sum combined
  across the world by the same reduce-scatter + all-gather primitive as
  the stats exchange (no all-reduce op enters the HLO), then scales
  exactly as ``optax.clip_by_global_norm`` would, BEFORE the 1/N
  update.  Other global-reduction transforms still need
  ``TFOS_SHARDED_UPDATE=0``.
- **model-parallel meshes opt out cleanly**: ``tp``/``sp``/``pp``/``ep``
  collectives live *inside* the model (GSPMD constraints, ring attention,
  GPipe) and do not compose with a data-axis manual region, so those
  meshes — and models prescribing their own sharded step or collection
  shardings (wide&deep) — keep the monolithic path
  (:func:`mesh_eligibility` names the reason).
- **buffer donation** and ``Trainer.attach_elastic``'s step-boundary
  regroup ride the unchanged ``compile_step`` plumbing.

``TFOS_BUCKETED_ALLREDUCE=0`` opts back into the monolithic step.
"""

from __future__ import annotations

import logging
import os
from typing import Any, Callable, Sequence

from tensorflowonspark_tpu.parallel import mesh as mesh_lib

logger = logging.getLogger(__name__)

#: the data-parallel mesh axes a gradient all-reduce spans: dp and fsdp
#: *are* the data-parallel world under ZeRO (the batch-axis split of
#: ``mesh.batch_spec`` minus ``ep``, which :data:`MODEL_AXES` bars from
#: this path — an ep>1 mesh keeps the monolithic step because MoE's token
#: all_to_alls live inside the model, so the size-1 ep axis never needs
#: to appear in these collectives)
DATA_AXES = ("dp", "fsdp")

#: mesh axes whose collectives live inside the model, not on the gradient
#: exchange — any of these sized >1 keeps the monolithic step (``ep``
#: included: expert-parallel gradient bucketing is future work, see
#: ROADMAP item 2's remaining opportunities)
MODEL_AXES = ("tp", "sp", "pp", "ep")

#: default bucket size (MiB).  Sized against the PR 2 ICI roofline probe:
#: the probe's delivered-bandwidth plateau starts at single-digit-MB
#: payloads (its own working set is ``_default_bytes()/4`` ≈ 8 MB/device on
#: accelerators), while per-collective launch latency is ~10 µs — at
#: 4 MiB a v4 ICI link (~2.4e10 B/s algorithmic) spends ~350 µs moving
#: bytes, ~35× the launch cost, yet a ResNet-50-sized gradient set still
#: splits into ~25 buckets to pipeline.  See DEPLOY.md for the sizing
#: arithmetic.
DEFAULT_BUCKET_MB = 4.0

#: DCN-tier sizing constants: per-collective launch+latency over the
#: data-centre network is ~ms, not ~10 µs, so cross-slice buckets must be
#: far bigger before wire time dominates.  ``dcn_bucket_bytes_default``
#: sizes them as ``DEFAULT_DCN_BUCKET_RATIO ×`` the ICI bound, capped
#: (DEPLOY.md arithmetic).
DEFAULT_DCN_BUCKET_RATIO = 4.0
_DCN_BUCKET_CAP = 64 * 1024 * 1024


def bucketing_enabled() -> bool:
    """``TFOS_BUCKETED_ALLREDUCE`` gate, default ON (re-read per call so
    tests and the bench A/B can toggle it live)."""
    return os.environ.get("TFOS_BUCKETED_ALLREDUCE", "1").strip().lower() \
        not in ("0", "false", "no")


def sharded_update_enabled() -> bool:
    """``TFOS_SHARDED_UPDATE`` gate, default ON: reduce-scatter buckets
    with the in-region 1/N optimizer update.  Global-norm clipping no
    longer needs this turned off — pass ``clip_global_norm=`` and the
    norm is computed as sharded partials combined by reduce-scatter +
    all-gather (module docstring's composition contract).  Turn OFF only
    for optimizer chains with *other* cross-param global reductions."""
    return os.environ.get("TFOS_SHARDED_UPDATE", "1").strip().lower() \
        not in ("0", "false", "no")


def bucket_bytes_default() -> int:
    """ICI-tier bucket size in bytes: ``TFOS_ALLREDUCE_BUCKET_MB``
    override, else :data:`DEFAULT_BUCKET_MB`."""
    env = os.environ.get("TFOS_ALLREDUCE_BUCKET_MB", "")
    try:
        mb = float(env) if env else DEFAULT_BUCKET_MB
    except ValueError:
        mb = DEFAULT_BUCKET_MB
    return max(1, int(mb * 1024 * 1024))


def dcn_bucket_bytes_default() -> int:
    """DCN-tier bucket size in bytes: ``TFOS_DCN_BUCKET_MB`` override,
    else :data:`DEFAULT_DCN_BUCKET_RATIO` × the ICI bound, capped."""
    env = os.environ.get("TFOS_DCN_BUCKET_MB", "")
    try:
        if env:
            return max(1, int(float(env) * 1024 * 1024))
    except ValueError:
        pass
    return min(int(bucket_bytes_default() * DEFAULT_DCN_BUCKET_RATIO),
               _DCN_BUCKET_CAP)


def mesh_eligibility(mesh, collection_shardings=None) -> tuple[bool, str]:
    """Can the bucketed step run on this mesh/model combination?

    Returns ``(ok, reason)`` — the reason names exactly why the monolithic
    step is kept, so the fallback is observable, not silent.
    """
    for axis in MODEL_AXES:
        if mesh.shape.get(axis, 1) > 1:
            return False, (
                f"mesh axis {axis!r} > 1: model-internal collectives "
                "(tensor/sequence/pipeline/expert) do not compose with a "
                "data-axis manual region")
    if data_parallel_world(mesh) < 2:
        return False, ("single data shard: no cross-replica gradient "
                       "exchange to bucket")
    if collection_shardings:
        return False, ("model-prescribed collection shardings: collections "
                       "cannot be treated as replicated inside the manual "
                       "region")
    return True, "eligible"


def data_parallel_world(mesh) -> int:
    """Participants in the gradient exchange (``dp × fsdp``; ``ep`` is
    barred from this path by :data:`MODEL_AXES`)."""
    return int(mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1))


def scatter_stages(mesh, mesh_config=None
                   ) -> tuple[list[tuple[str, ...]], int, str | None]:
    """Per-tier collective staging for the data-axis exchange.

    Returns ``(stages, dcn_world, reason)``: ``stages`` is the ordered
    list of axis-name tuples a reduce-scatter walks (all-gathers invert
    it); their flattened concatenation is the dim-0 layout the scattered
    shards land in (``P((flattened...), ...)``) — verified property of
    ``psum_scatter``: a joint-tuple scatter and a sequential per-axis
    scatter both place block *k* on the device with
    ``axis_index((flattened...)) == k``.  ``dcn_world`` is the stage-2
    participant count (1 when single-tier).

    Two-tier staging needs the ``Mesh``'s provenance: the mesh object
    does not record which axes cross slices, so callers thread the
    :class:`mesh.MeshConfig` it was built from.  A named axis cannot be
    subdivided by a collective, so the DCN axis must be *purely*
    cross-slice (size == ``slices``; ``hybrid_device_array`` lays it out
    slice-major) — otherwise single-tier with the reason returned.
    """
    axes = tuple(a for a in DATA_AXES if mesh.shape.get(a, 1) > 1) \
        or (DATA_AXES[0],)
    if mesh_config is None:
        return [axes], 1, None
    cfg = mesh_config
    try:
        cfg = mesh_config.resolve(int(mesh.devices.size))
    except Exception:
        pass
    slices = int(getattr(cfg, "slices", 1) or 1)
    if slices <= 1:
        return [axes], 1, None
    try:
        dcn = cfg.dcn_axis()
    except ValueError as e:
        return [axes], 1, f"no DCN-capable data axis: {e}"
    if mesh.shape.get(dcn, 1) != slices:
        return [axes], 1, (
            f"dcn axis {dcn!r} size {mesh.shape.get(dcn, 1)} != slices "
            f"{slices}: the axis mixes in-slice and cross-slice "
            "neighbours and a named-axis collective cannot subdivide it "
            "— single-tier fallback")
    ici = tuple(a for a in axes if a != dcn)
    if not ici:
        return [(dcn,)], slices, None
    return [ici, (dcn,)], slices, None


def leaf_bytes(leaf) -> int:
    """Gradient bytes one param leaf contributes to the exchange."""
    size = int(getattr(leaf, "size", 0) or 0)
    dtype = getattr(leaf, "dtype", None)
    itemsize = getattr(dtype, "itemsize", 4) if dtype is not None else 4
    return size * itemsize


def scatter_eligible(leaf, world: int, min_bytes: int) -> bool:
    """Does this param leaf take the reduce-scatter update path?  Floating
    dtype plus the :func:`shapes.update_shard_eligible` shape policy
    (dim-0 divides the world; at least ``min_bytes`` big)."""
    import jax.numpy as jnp

    from tensorflowonspark_tpu import shapes

    dtype = getattr(leaf, "dtype", None)
    if dtype is None or not jnp.issubdtype(dtype, jnp.inexact):
        return False
    return shapes.update_shard_eligible(
        tuple(getattr(leaf, "shape", ())), int(getattr(dtype, "itemsize", 4)),
        world, min_bytes)


def partition_buckets(leaves: Sequence[Any], bucket_bytes: int,
                      keys: Sequence[Any] | None = None) -> list[list[int]]:
    """Partition param leaves (by flatten index) into size-bounded buckets.

    Deterministic — a pure function of flatten order, sizes and ``keys``,
    so every process of a multi-host job builds the identical collective
    schedule:

    - a leaf of ``>= bucket_bytes`` stands alone (never split: one leaf =
      one array = one collective operand);
    - smaller leaves coalesce greedily in flatten order until the next
      leaf would push the bucket past ``bucket_bytes``;
    - a bucket never spans a ``keys`` boundary: ``keys[i] != keys[j]``
      forces leaves *i* and *j* into different buckets.  Callers key on
      ``(dtype, scatter-eligibility)`` — concatenating f32 and bf16
      segments would silently upcast (inflating collective bytes and
      skewing :func:`collective_bytes_per_step`), and a scatter bucket
      must not absorb a replicated-path leaf.
    """
    buckets: list[list[int]] = []
    cur: list[int] = []
    cur_bytes = 0
    cur_key = None
    for i, leaf in enumerate(leaves):
        nb = leaf_bytes(leaf)
        key = keys[i] if keys is not None else None
        if nb >= bucket_bytes:
            if cur:
                buckets.append(cur)
                cur, cur_bytes = [], 0
            buckets.append([i])
            continue
        if cur and (cur_bytes + nb > bucket_bytes or key != cur_key):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nb
        cur_key = key
    if cur:
        buckets.append(cur)
    return buckets


def ideal_serial_allreduce_seconds(nbytes: int, n_devices: int,
                                   bw_gbps: float | None) -> float | None:
    """Serial (zero-overlap) wall cost of all-reducing ``nbytes`` of
    gradients across ``n_devices`` at the *delivered* interconnect
    bandwidth — the denominator of ``allreduce_overlap_frac``.

    Uses the ring algorithmic-bandwidth convention ``2·S·(n-1)/n``,
    matching how ``obs/roofline.py::measure_ici_bandwidth`` reports
    ``ici_bw_gbps``, so exposed-comm-time divides by a like-for-like
    ideal.  ``None`` when there is no bandwidth figure or no interconnect.
    """
    if not bw_gbps or bw_gbps <= 0 or n_devices < 2 or nbytes <= 0:
        return None
    moved = 2.0 * float(nbytes) * (n_devices - 1) / n_devices
    return moved / (bw_gbps * 1e9)


def _staged_oneway_bytes(nbytes: float, ici_n: int, dcn_n: int
                         ) -> tuple[float, float]:
    """One collective pass (a reduce-scatter OR an all-gather) of
    ``nbytes`` per replica over a two-tier ring, split ``(ici, dcn)``:
    the in-tier stage moves ``S·(n₁-1)/n₁``, the cross-tier stage moves
    the surviving ``S/n₁`` shard at ``(n₂-1)/n₂``.  Sums to the flat-ring
    ``S·(N-1)/N`` — staging moves the same total, it just pins most of it
    to the fast tier."""
    ici = nbytes * (ici_n - 1) / ici_n if ici_n > 1 else 0.0
    rem = nbytes / max(ici_n, 1)
    dcn = rem * (dcn_n - 1) / dcn_n if dcn_n > 1 else 0.0
    return ici, dcn


def collective_bytes_per_step(param_leaves: Sequence[Any], world: int, *,
                              scatter_min_bytes: int | None = None,
                              dcn_world: int = 1,
                              update_shard: bool = True) -> dict[str, Any]:
    """Analytic per-replica collective bytes for one train step, allreduce
    path vs reduce-scatter/sharded-update path — the model ``bench.py
    --collectives`` stamps and ``tools/bench_gate.py`` gates (r19).

    Accounting convention (ring algorithmic bytes, per replica):

    - ``exchange``: bytes on the *gradient-exchange* leg — everything
      that must move before the optimizer update can complete.  Allreduce
      path: ``2·S·(N-1)/N`` (reduce-scatter + all-gather phases of the
      ring, both pre-update).  Scatter path: ``S_e·(N-1)/N`` for the
      scatter-eligible bytes (one pass — the gather moves *parameters*,
      after the update) plus ``2·S_r·(N-1)/N`` for replicated-fast-path
      leaves plus the (tiny) loss/stats segment.
    - ``gather``: the post-update parameter all-gather
      (``S_e·(N-1)/N``; zero on the allreduce path, where updated params
      never move).  It overlaps the next forward (the PR 12 property), so
      it is off the exchange critical path — but it is NOT free, which is
      why ``total`` is reported beside the headline.
    - ``total`` = exchange + gather.  Totals of the two paths converge —
      the sharded update's wins are the *halved exchange leg* (the part
      serialized against backward), the 1/N update FLOPs, and the 1/N
      optimizer-state memory, not fewer total wire bytes.

    ``exchange_ratio`` (scatter.exchange / allreduce.exchange) is the
    headline: → ½ as the eligible fraction → 1 ("≈½ asymptotically"),
    1.0 when nothing is eligible or ``update_shard`` is off.  Per-tier
    splits (``*_ici`` / ``*_dcn``) use :func:`_staged_oneway_bytes` when
    ``dcn_world > 1``.  The loss/stats segment is modelled as the
    world-padded loss scalar only — collection traffic is model-dependent
    and negligible at the same order.
    """
    if scatter_min_bytes is None:
        from tensorflowonspark_tpu.parallel.train import zero_min_bytes

        scatter_min_bytes = zero_min_bytes()
    dcn_world = max(1, int(dcn_world))
    ici_world = max(1, world // dcn_world)
    total = elig = 0
    n_elig = 0
    for leaf in param_leaves:
        nb = leaf_bytes(leaf)
        total += nb
        if update_shard and scatter_eligible(leaf, world, scatter_min_bytes):
            elig += nb
            n_elig += 1
    repl = total - elig
    stats = 4.0 * world  # the world-padded loss scalar segment

    def _path(exchange_passes: Sequence[float], gather_passes: float
              ) -> dict[str, float]:
        ex_i = ex_d = 0.0
        for nb in exchange_passes:
            i, d = _staged_oneway_bytes(nb, ici_world, dcn_world)
            ex_i += i
            ex_d += d
        ga_i, ga_d = _staged_oneway_bytes(gather_passes, ici_world, dcn_world)
        ex, ga = ex_i + ex_d, ga_i + ga_d
        return {"exchange": ex, "gather": ga, "total": ex + ga,
                "exchange_ici": ex_i, "exchange_dcn": ex_d,
                "gather_ici": ga_i, "gather_dcn": ga_d}

    allreduce = _path([2.0 * total], 0.0)
    if update_shard:
        scatter = _path([1.0 * elig, 2.0 * repl, 2.0 * stats], 1.0 * elig)
    else:
        scatter = _path([2.0 * total], 0.0)
    ratio = (scatter["exchange"] / allreduce["exchange"]
             if allreduce["exchange"] > 0 else None)
    return {
        "world": int(world), "dcn_world": dcn_world, "ici_world": ici_world,
        "grad_bytes": int(total), "scatter_bytes": int(elig),
        "replicated_bytes": int(repl),
        "n_leaves": len(list(param_leaves)), "n_scatter_leaves": n_elig,
        "update_shard": bool(update_shard),
        "allreduce": allreduce, "scatter": scatter,
        "exchange_ratio": ratio,
    }


def _cross_replica_mean_collections(cols):
    """``pmean`` floating collection leaves over the data axes (running
    batch statistics become global-batch means); non-float leaves (step
    counters etc.) pass through as local values."""
    import jax
    import jax.numpy as jnp

    def _one(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.inexact):
            return jax.lax.pmean(x, DATA_AXES)
        return x

    return jax.tree_util.tree_map(_one, cols)


def make_bucketed_train_step(
    loss_fn: Callable[..., Any],
    optimizer,
    mesh,
    param_shardings,
    state,
    batch_example: Any,
    sequence_axes: dict[str, int] | None = None,
    donate: bool = True,
    collection_shardings=None,
    bucket_bytes: int | None = None,
    reduce: bool = True,
    update_shard: bool | None = None,
    mesh_config=None,
    scatter_min_bytes: int | None = None,
    clip_global_norm: float | None = None,
):
    """Compile the bucketed-collective ``state, batch -> state, loss`` step.

    Same contract as :func:`train.make_train_step` (which dispatches here
    when :func:`mesh_eligibility` holds), plus:

    - ``bucket_bytes``: bucket bound (default :func:`bucket_bytes_default`,
      raised to :func:`dcn_bucket_bytes_default` when the exchange stages
      over DCN);
    - ``update_shard``: the sharded-update structure (default
      :func:`sharded_update_enabled`; forced off for the no-reduce twin);
    - ``mesh_config``: the :class:`mesh.MeshConfig` the mesh was built
      from, enabling two-tier staging on multi-slice topologies
      (:func:`scatter_stages`);
    - ``scatter_min_bytes``: scatter-eligibility size floor (default
      ``train.zero_min_bytes()`` — the shared ``TFOS_ZERO_MIN_BYTES``
      knob);
    - ``clip_global_norm``: optional global-norm gradient clip applied
      before the optimizer update, exact ``optax.clip_by_global_norm``
      semantics.  On the sharded-update path each replica's
      scatter-eligible gradient shards tile the full gradient, so the
      cross-replica sum of shard square-sums (one extra scalar
      reduce-scatter + all-gather — no all-reduce op enters the HLO)
      plus the replicated leaves' square-sum is the exact global square
      norm; clipped optimizers keep the reduce-scatter path instead of
      needing ``TFOS_SHARDED_UPDATE=0``;
    - ``reduce=False`` compiles the *no-reduce* twin — identical graph
      minus the per-bucket gradient collectives — used by ``bench.py`` to
      measure the compute-only floor an overlap fraction is judged
      against.  Its numbers are NOT a valid training step.

    The returned step carries the bucket/comm metadata the trainer and
    bench read: ``.bucketed`` (True), ``.n_buckets``, ``.bucket_bytes``,
    ``.comm_bytes`` (gradient bytes crossing replicas per step),
    ``.data_world`` (exchange participants), ``.update_sharded``,
    ``.n_scatter_buckets`` / ``.n_replicated_buckets`` /
    ``.n_stats_segments`` (the HLO reduce-scatter/all-gather op count is
    their sum × ``.n_tiers``), ``.scatter_axes``, ``.n_tiers``,
    ``.dcn_world``, ``.tier_reason`` and ``.comm_model``
    (:func:`collective_bytes_per_step`).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tensorflowonspark_tpu.parallel.train import (
        TrainState, compile_step, path_keys, state_shardings, zero_min_bytes)

    ok, reason = mesh_eligibility(mesh, collection_shardings)
    if not ok:
        raise ValueError(f"bucketed train step unavailable: {reason}")

    stateful = bool(getattr(loss_fn, "stateful", False))
    param_leaves, param_treedef = jax.tree_util.tree_flatten(state.params)
    world = data_parallel_world(mesh)
    stages, dcn_world, tier_reason = scatter_stages(mesh, mesh_config)
    scatter_axes = tuple(a for st in stages for a in st)
    if update_shard is None:
        update_shard = sharded_update_enabled()
    update_shard = bool(update_shard and reduce)
    min_bytes = (zero_min_bytes() if scatter_min_bytes is None
                 else int(scatter_min_bytes))
    eligible = [update_shard and scatter_eligible(leaf, world, min_bytes)
                for leaf in param_leaves]
    if bucket_bytes is None:
        bucket_bytes = bucket_bytes_default()
        if dcn_world > 1:
            bucket_bytes = max(bucket_bytes, dcn_bucket_bytes_default())
    keys = [(str(getattr(leaf, "dtype", "f32")), eligible[i])
            for i, leaf in enumerate(param_leaves)]
    buckets = partition_buckets(param_leaves, bucket_bytes, keys=keys)
    kinds = ["scatter" if eligible[b[0]] else "repl" for b in buckets]
    comm_bytes = sum(leaf_bytes(leaf) for leaf in param_leaves)
    shapes_ = [tuple(getattr(leaf, "shape", ())) for leaf in param_leaves]
    sizes = [int(getattr(leaf, "size", 0)) for leaf in param_leaves]

    def _rs(mat):
        for axes in stages:
            mat = jax.lax.psum_scatter(mat, axes, scatter_dimension=0,
                                       tiled=True)
        return mat

    def _ag(mat):
        for axes in reversed(stages):
            mat = jax.lax.all_gather(mat, axes, axis=0, tiled=True)
        return mat

    def _rs_ag_sum(flat, n):
        """Full cross-replica SUM of a flat length-``n`` vector via
        reduce-scatter + all-gather (pad to the world, scatter row
        blocks, gather them back) — byte-equivalent to an all-reduce but
        the same HLO op family as the rest of the sharded step, keeping
        the lowered module free of ``all-reduce`` ops."""
        c = -(-n // world)
        if c * world != n:
            flat = jnp.pad(flat, (0, c * world - n))
        return _ag(_rs(flat.reshape(world, c))).reshape(-1)[:n]

    # loss/collections stats segments (sharded-update path only): the
    # loss scalar is its own segment; floating collection leaves group by
    # dtype (deterministic order — every process builds the same ops)
    col_leaves0, col_treedef = jax.tree_util.tree_flatten(state.collections)
    col_groups: dict[str, list[int]] = {}
    for i, leaf in enumerate(col_leaves0):
        dt = getattr(leaf, "dtype", None)
        if dt is not None and jnp.issubdtype(dt, jnp.inexact):
            col_groups.setdefault(str(dt), []).append(i)
    stats_groups = sorted(col_groups.items())
    n_stats_segments = 1 + (len(stats_groups) if stateful else 0)

    def _stats_exchange(loss, cols):
        loss = (_rs_ag_sum(loss.reshape(1), 1) / world).reshape(())
        if not stateful:
            return loss, cols
        leaves = jax.tree_util.tree_leaves(cols)
        out = list(leaves)
        for _dt, idxs in stats_groups:
            parts = [leaves[i].reshape(-1) for i in idxs]
            flat = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
            n = sum(int(col_leaves0[i].size) for i in idxs)
            flat = _rs_ag_sum(flat, n) / world
            off = 0
            for i in idxs:
                sz = int(col_leaves0[i].size)
                out[i] = flat[off:off + sz].reshape(col_leaves0[i].shape)
                off += sz
        return loss, jax.tree_util.tree_unflatten(col_treedef, out)

    def _local_loss_grads(params, collections, batch):
        """Per-data-shard loss/grads.  The local loss is the mean over
        this shard's examples; the cross-replica mean of equal-sized
        shard means is exactly the global-batch mean, so losses and
        gradients match the monolithic step to f32 reduction order."""
        if stateful:
            (loss, new_cols), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, collections, batch)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            new_cols = collections
        return loss, new_cols, grads

    def _batch_in_spec(leaf):
        ndim = getattr(leaf, "ndim", 0)
        if not ndim:
            return P()
        return P(*([DATA_AXES] + [None] * (ndim - 1)))

    replicated = lambda tree: jax.tree_util.tree_map(lambda _: P(), tree)  # noqa: E731
    batch_specs = jax.tree_util.tree_map(_batch_in_spec, batch_example)

    if update_shard:
        # optimizer-state leaves of scatter-eligible params are STORED as
        # the dim-0 slice their psum_scatter block lands on, so the
        # scattered gradient shard and the opt state meet on-device with
        # no resharding hop.  opt_param_shardings drives the storage
        # (train.state_shardings); opt_in_specs drives the region entry —
        # matched by the same path-suffix + shape rule.
        param_sh_leaves = jax.tree_util.tree_leaves(
            param_shardings, is_leaf=lambda x: hasattr(x, "spec"))
        flat_params_p = jax.tree_util.tree_flatten_with_path(state.params)[0]
        elig_by_path = {
            path_keys(path): shapes_[i]
            for i, (path, _leaf) in enumerate(flat_params_p) if eligible[i]
        }
        opt_param_shardings = jax.tree_util.tree_unflatten(param_treedef, [
            mesh_lib.named_sharding(
                mesh, scatter_axes, *([None] * (len(shapes_[i]) - 1)))
            if eligible[i] else param_sh_leaves[i]
            for i in range(len(param_leaves))
        ])

        def _opt_spec(path, leaf):
            norm = path_keys(path)
            shape = tuple(getattr(leaf, "shape", ()))
            for i in range(len(norm)):
                hit = elig_by_path.get(norm[i:])
                if hit is not None and hit == shape:
                    return P(scatter_axes, *([None] * (len(shape) - 1)))
            return P()

        opt_in_specs = jax.tree_util.tree_map_with_path(
            _opt_spec, state.opt_state)

        def _local_step(params, opt_state, collections, batch):
            import optax

            loss, new_cols, grads = _local_loss_grads(
                params, collections, batch)
            grad_leaves = jax.tree_util.tree_leaves(grads)
            p_leaves = jax.tree_util.tree_leaves(params)
            shard_grads: dict[int, Any] = {}
            full_grads: dict[int, Any] = {}
            # one reduce-scatter per bucket (replicated buckets add their
            # gather-back), issued in reverse flatten order — the order
            # backward produces gradients, so the scheduler overlaps each
            # exchange with the backward still running
            for bucket, kind in zip(reversed(buckets), reversed(kinds)):
                if kind == "scatter":
                    mat = jnp.concatenate(
                        [grad_leaves[i].reshape(world, -1) for i in bucket],
                        axis=1) if len(bucket) > 1 \
                        else grad_leaves[bucket[0]].reshape(world, -1)
                    mat = _rs(mat) / world
                    off = 0
                    for i in bucket:
                        n = sizes[i] // world
                        seg = mat[:, off:off + n]
                        shard_grads[i] = seg.reshape(
                            (shapes_[i][0] // world,) + shapes_[i][1:])
                        off += n
                else:
                    flat = jnp.concatenate(
                        [grad_leaves[i].reshape(-1) for i in bucket]) \
                        if len(bucket) > 1 \
                        else grad_leaves[bucket[0]].reshape(-1)
                    n = sum(sizes[i] for i in bucket)
                    flat = _rs_ag_sum(flat, n) / world
                    off = 0
                    for i in bucket:
                        full_grads[i] = \
                            flat[off:off + sizes[i]].reshape(shapes_[i])
                        off += sizes[i]
            loss, new_cols = _stats_exchange(loss, new_cols)
            # the 1/N update: each replica updates only the parameter
            # rows its scattered gradient block covers — valid because
            # the transforms are elementwise (module docstring contract)
            k = jax.lax.axis_index(scatter_axes)
            g_list, p_list = [], []
            for i in range(len(param_leaves)):
                if eligible[i]:
                    rows = shapes_[i][0] // world
                    p_list.append(jax.lax.dynamic_slice_in_dim(
                        p_leaves[i], k * rows, rows, axis=0))
                    g_list.append(shard_grads[i])
                else:
                    p_list.append(p_leaves[i])
                    g_list.append(full_grads[i])
            if clip_global_norm is not None:
                # global-norm clip on sharded gradients: eligible leaves'
                # shards are disjoint row blocks tiling the full (already
                # cross-replica-averaged) gradient, so summing their
                # square-sums across the world — via the same rs+ag
                # primitive as the stats exchange, never an all-reduce —
                # plus the replicated leaves' square-sum (identical on
                # every replica, added once) is the exact global square
                # norm optax.clip_by_global_norm would see
                zero = jnp.float32(0.0)
                shard_sq = sum(
                    (jnp.sum(jnp.square(g_list[i]))
                     for i in range(len(param_leaves)) if eligible[i]),
                    zero)
                repl_sq = sum(
                    (jnp.sum(jnp.square(g_list[i]))
                     for i in range(len(param_leaves)) if not eligible[i]),
                    zero)
                total_sq = repl_sq + _rs_ag_sum(
                    shard_sq.reshape(1), 1).reshape(())
                g_norm = jnp.sqrt(total_sq)
                c = jnp.float32(clip_global_norm)
                g_list = [
                    jnp.where(g_norm < c, g,
                              (g / g_norm.astype(g.dtype)) * c)
                    for g in g_list
                ]
            g_tree = jax.tree_util.tree_unflatten(param_treedef, g_list)
            p_tree = jax.tree_util.tree_unflatten(param_treedef, p_list)
            with jax.named_scope("optimizer"):
                updates, new_opt = optimizer.update(g_tree, opt_state,
                                                    p_tree)
                new_p = jax.tree_util.tree_leaves(
                    optax.apply_updates(p_tree, updates))
            out = []
            for i in range(len(param_leaves)):
                # updated shards gather back per leaf as each update's
                # dataflow completes — off the exchange critical path,
                # overlapping the next forward (the PR 12 property)
                out.append(_ag(new_p[i]) if eligible[i] else new_p[i])
            return loss, new_cols, tuple(out), new_opt

        smapped = mesh_lib.shard_map_unchecked(
            _local_step, mesh,
            in_specs=(replicated(state.params), opt_in_specs,
                      replicated(state.collections), batch_specs),
            out_specs=(P(), replicated(state.collections),
                       tuple(P() for _ in param_leaves), opt_in_specs),
        )

        def _step(st: TrainState, batch):
            loss, new_cols, new_params, new_opt = smapped(
                st.params, st.opt_state, st.collections, batch)
            params = jax.tree_util.tree_unflatten(
                param_treedef, list(new_params))
            return TrainState(params, new_opt, st.step + 1, new_cols), loss

        step = compile_step(_step, mesh, param_shardings, state,
                            batch_example, sequence_axes=sequence_axes,
                            donate=donate,
                            collection_shardings=collection_shardings,
                            opt_param_shardings=opt_param_shardings)
        # the storage layout the compiled step expects for the optimizer
        # state: a caller whose opt state was eagerly initialized against
        # the PARAM layout (committed arrays — Trainer.__init__) must
        # device_put it to this tree once before the first step
        step.opt_state_shardings = state_shardings(
            state, param_shardings, mesh,
            collection_shardings=collection_shardings,
            opt_param_shardings=opt_param_shardings).opt_state
    else:
        def _local_grads(params, collections, batch):
            loss, new_cols, grads = _local_loss_grads(
                params, collections, batch)
            grad_leaves = jax.tree_util.tree_leaves(grads)
            reduced = list(grad_leaves)
            if reduce:
                # one variadic collective per bucket, issued in reverse
                # flatten order — the order backward produces gradients,
                # so the scheduler can overlap each reduction with the
                # rest of the backward still running
                for bucket in reversed(buckets):
                    vals = jax.lax.pmean(
                        [grad_leaves[i] for i in bucket], DATA_AXES)
                    for i, v in zip(bucket, vals):
                        reduced[i] = v
            loss = jax.lax.pmean(loss, DATA_AXES)
            if stateful:
                new_cols = _cross_replica_mean_collections(new_cols)
            return loss, new_cols, tuple(reduced)

        smapped = mesh_lib.shard_map_unchecked(
            _local_grads, mesh,
            in_specs=(replicated(state.params),
                      replicated(state.collections), batch_specs),
            out_specs=(P(), replicated(state.collections),
                       tuple(P() for _ in param_leaves)),
        )

        def _step(st: TrainState, batch):
            loss, new_cols, reduced = smapped(
                st.params, st.collections, batch)
            grads = jax.tree_util.tree_unflatten(param_treedef, list(reduced))
            import optax

            if clip_global_norm is not None:
                # full reduced gradients are in hand here, so the stock
                # optax transform gives the reference clip semantics
                grads, _ = optax.clip_by_global_norm(
                    float(clip_global_norm)).update(
                        grads, optax.EmptyState())
            # one optax call, per-leaf dataflow: each param's update/apply
            # depends only on its own bucket's reduction (plus the scalar
            # count), so XLA schedules bucket i's weight update behind
            # bucket i's all-reduce while later buckets are still reducing
            with jax.named_scope("optimizer"):
                updates, opt_state = optimizer.update(
                    grads, st.opt_state, st.params)
                params = optax.apply_updates(st.params, updates)
            return TrainState(params, opt_state, st.step + 1, new_cols), loss

        step = compile_step(_step, mesh, param_shardings, state,
                            batch_example, sequence_axes=sequence_axes,
                            donate=donate,
                            collection_shardings=collection_shardings)

    step.bucketed = True
    step.reduce = reduce
    step.n_buckets = len(buckets)
    step.bucket_bytes = bucket_bytes
    step.comm_bytes = comm_bytes
    step.data_world = world
    step.update_sharded = update_shard
    step.clip_global_norm = clip_global_norm
    step.n_scatter_buckets = kinds.count("scatter") if update_shard else 0
    step.n_replicated_buckets = kinds.count("repl") if update_shard else 0
    step.n_stats_segments = n_stats_segments if update_shard else 0
    step.scatter_axes = scatter_axes
    step.n_tiers = len(stages)
    step.dcn_world = dcn_world
    step.tier_reason = tier_reason
    step.comm_model = collective_bytes_per_step(
        param_leaves, world, scatter_min_bytes=min_bytes,
        dcn_world=dcn_world, update_shard=update_shard)
    return step
