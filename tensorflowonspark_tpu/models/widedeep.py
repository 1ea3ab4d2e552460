"""Criteo wide-and-deep CTR model — acceptance config #4 (``BASELINE.md``)
and half the headline metric (``BASELINE.json::metric`` — steps/sec).

Reference anchor: the estimator-era wide&deep example of the reference's
``examples/`` tree (``SURVEY.md §1 L6``).  Criteo layout: 13 integer (dense)
features + 26 categorical features pre-hashed into per-feature buckets.

TPU-first choices:

- all 26 categorical lookups run as ONE stacked gather over a single fused
  table (per-feature offsets added to the ids) instead of 26 small kernels —
  the batched-not-scalar rule of the MXU/HBM playbook.
- the embedding tables live OUTSIDE the optax parameter tree, in the
  ``"embedding"`` variable collection, and train with AdaGrad at
  ``Config.table_lr`` while the dense MLP tower trains through whatever
  optax optimizer the ``Trainer`` holds (AdamW by default) — the
  reference-era split (FTRL/AdaGrad on wide+embeddings, Adam-family on
  the dense tower), which measured 3.6× over AdamW-on-everything
  (``BENCH_NOTES.md``).
- ``Config.table_update`` names the AdaGrad VARIANT of the tables, not
  how it runs.  ``"dense"`` (the default) sums the gradients of a batch's
  duplicate ids before it squares them into the accumulator — what the
  gradient of a gather gives — and ``"sparse"``
  (``embedding.sparse_adagrad_update``) squares each occurrence on its
  own.  On batches that repeat an id they are two trajectories; see the
  ``Config.table_update`` comment.
- how ``"dense"`` EXECUTES is chosen when a batch shape is traced, from
  static shapes alone (:func:`update_touches_rows`: the table rows a device
  holds against the ids a step looks up).  A table large for its batch is
  updated on the looked-up rows: the gradient w.r.t. the gathered rows,
  duplicates summed by one equality-mask product a feature, two row
  scatters a table (``embedding.adagrad_update_rows``); nothing of a
  table's shape is allocated.  A table small for its batch takes the
  gather's VJP and one pass over the whole table, which is cheaper there.
  The two meet at about 160 table rows an id on a v5e chip
  (``ROWS_PER_ID_CROSSOVER`` and the measurements beside it;
  BENCH_NOTES.md has the older 2.6 M-row ones), agree to float32 rounding
  and both leave untouched rows bit-identical (``tests/test_models.py``).
- :func:`make_sharded_train_step` is the model-supplied custom step the
  ``Trainer`` picks up; it composes with the generic machinery through
  ``parallel.train.compile_step`` (same shardings, donation, active mesh).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from tensorflowonspark_tpu import obs

NUM_DENSE = 13
NUM_CAT = 26


@dataclasses.dataclass(frozen=True)
class Config:
    hash_buckets: int = 100_000  # per categorical feature
    embed_dim: int = 32
    hidden: tuple = (1024, 512, 256)
    # f32 everywhere: an in-process A/B on the bench chip measured bf16 MLP
    # compute at parity with f32 (22.6-22.9 ms/step all variants — the step
    # is scatter/table-bound, BENCH_NOTES.md), so bf16's precision cost
    # buys nothing here; tables especially must stay f32 (AdaGrad's late
    # small updates fall below bf16's ~3 decimal digits)
    dtype: str = "float32"
    table_dtype: str = "float32"
    table_lr: float = 0.01  # AdaGrad rate for wide+embedding tables
    # The AdaGrad variant of the tables, not how it runs:
    # "dense": combined accumulation — the gradients of a batch's duplicate
    #   ids are SUMMED, then squared into the accumulator (what the gradient
    #   of a gather gives).  Runs as a pass over the whole table or on the
    #   looked-up rows alone, chosen from the shapes (update_touches_rows:
    #   the rows from 160 table rows an id, measured on a v5e chip); the
    #   same numbers either way.
    # "sparse": per-occurrence accumulation — embedding.sparse_adagrad_update
    #   squares each occurrence separately, on the gathered rows.
    # NOT numerically identical when a batch repeats an id, so switching
    # changes the training trajectory on duplicate-heavy data, not just the
    # speed.  Both are legitimate AdaGrad variants; pick one per run and
    # keep it.
    table_update: str = "dense"

    @classmethod
    def tiny(cls) -> "Config":
        return cls(hash_buckets=50, embed_dim=4, hidden=(16,))

    @property
    def total_buckets(self) -> int:
        return self.hash_buckets * NUM_CAT


SEQUENCE_AXES: dict = {}


def fold_ids(cat, config: Config):
    """(B, 26) per-feature ids -> (B, 26) global ids into the fused table."""
    import jax.numpy as jnp

    offsets = jnp.arange(NUM_CAT, dtype=cat.dtype) * config.hash_buckets
    return cat + offsets[None, :]


#: Table rows a looked-up id from which the default (``"dense"``) AdaGrad
#: touches only the batch's rows and no longer passes over the whole table.
#: Whole step on one TPU v5e chip, ms (PR 30's chip runs;
#: ``tools/table_update_crossover.py`` measures the table again):
#:
#: ======= ===== ========= ========= ========= =======================
#: buckets batch rows / id rows      full pass
#: ======= ===== ========= ========= ========= =======================
#: 650,000 1,024       635      9.97     24.70 the benchmark's cell
#: 650,000 4,096       159     40.19     39.43 the threshold, within 2%
#: 100,000 1,024        98      9.63      7.92
#: 100,000 4,096        24     39.71     21.80 ``Config()``, ``bench.py``
#: ======= ===== ========= ========= ========= =======================
#:
#: PR 31 measured the two ends again (rows / full pass): 9.97 / 24.71 at
#: 650,000 x 1,024 and 39.72 / 21.80 at 100,000 x 4,096.
#: The pass costs about 1.2 ns a table row whatever the batch, plus 174 ns
#: an id for its own gather and VJP scatter; the rows execution costs 378 ns
#: an id whatever the table (two row scatters and one more row gather a
#: table).  They meet at (378 - 174) / 1.2, about 160 rows an id.
#:
#: Not measured: (1) vocab-sharded tables.  The rule takes the rows ONE
#: device holds (``total_buckets // tp``), reasoning that each device passes
#: over its own rows while every device still handles every id; no
#: multi-chip run has timed either side, so the ``// tp`` is inferred.
#: (2) Batches over 4,096: the rule is linear in the ids and the
#: duplicate-summing mask product is not (``F * B * B * E``: 9 ns an id at
#: 4,096, 37 at 16,384 by that count).
ROWS_PER_ID_CROSSOVER = 160


def update_touches_rows(table_rows: int, ids_per_step: int) -> bool:
    """How the ``"dense"`` table update of a step executes: on the rows the
    batch looked up (True) or as a pass over the whole table (False).  A
    pure function of static shapes: the rows of a table one device holds
    and the ids a step looks up."""
    return table_rows >= ROWS_PER_ID_CROSSOVER * ids_per_step


def make_model(config: Config, mesh=None):
    import flax.linen as nn
    import jax.numpy as jnp

    dtype = jnp.dtype(config.dtype)
    table_dtype = jnp.dtype(getattr(config, "table_dtype", "float32"))

    class WideDeep(nn.Module):
        """``__call__(dense, cat)`` gathers internally (init / eval path);
        the sparse train step passes pre-gathered ``emb_rows``/``wide_rows``
        so it can take gradients w.r.t. exactly the touched rows."""

        @nn.compact
        def __call__(self, dense, cat, emb_rows=None, wide_rows=None):
            deep_table = self.variable(
                "embedding", "deep",
                lambda: nn.initializers.normal(stddev=0.01)(
                    self.make_rng("params"),
                    (config.total_buckets, config.embed_dim), table_dtype,
                ),
            )
            wide_table = self.variable(
                "embedding", "wide",
                lambda: jnp.zeros((config.total_buckets,), table_dtype),
            )
            # per-row AdaGrad accumulators for the sparse engine; created at
            # init so they ride the same collections/checkpoint machinery,
            # but NOT required at apply time (a serving export may carry
            # only params + the embedding tables)
            if self.is_initializing():
                self.variable(
                    "embedding_opt", "deep_acc",
                    lambda: jnp.zeros(
                        (config.total_buckets, config.embed_dim),
                        jnp.float32),
                )
                self.variable(
                    "embedding_opt", "wide_acc",
                    lambda: jnp.zeros((config.total_buckets,), jnp.float32),
                )

            if (emb_rows is None) != (wide_rows is None):
                raise ValueError(
                    "emb_rows and wide_rows must be passed together (the "
                    "sparse train step pre-gathers BOTH) or both omitted "
                    f"(the model gathers); got emb_rows="
                    f"{'set' if emb_rows is not None else 'None'}, "
                    f"wide_rows={'set' if wide_rows is not None else 'None'}"
                )
            if emb_rows is None:
                ids = fold_ids(cat, config)
                emb_rows = jnp.take(deep_table.value, ids, axis=0)  # (B,26,E)
                wide_rows = jnp.take(wide_table.value, ids, axis=0)  # (B,26)

            wide_logit = wide_rows.sum(axis=1)  # (B,)
            x = jnp.concatenate(
                [emb_rows.reshape(emb_rows.shape[0], -1).astype(dtype),
                 jnp.log1p(jnp.maximum(dense, 0.0)).astype(dtype)],
                axis=-1,
            )
            for h in config.hidden:
                x = nn.Dense(
                    h, dtype=dtype,
                    kernel_init=nn.with_partitioning(
                        nn.initializers.he_normal(), ("embed", "mlp")
                    ),
                )(x)
                x = nn.relu(x)
            deep_logit = nn.Dense(
                1, dtype=jnp.float32,
                kernel_init=nn.with_partitioning(
                    nn.initializers.lecun_normal(), ("embed", "classes")
                ),
            )(x)[:, 0]
            return wide_logit + deep_logit  # (B,) CTR logit

    return WideDeep()


def _apply(module, params, collections, batch, **rows):
    return module.apply(
        {"params": params, **collections},
        batch["dense"], batch["cat"], **rows,
    )


def make_loss_fn(module, config: Config):
    """Stateful loss for the GENERIC step path: reads the tables from the
    collections and returns them unchanged.  Note the generic optax path
    does not train the tables — table updates are the sparse step's job
    (:func:`make_sharded_train_step`, which the ``Trainer`` prefers
    automatically); this loss exists for API parity and eval-style use.
    """
    import jax.numpy as jnp
    import optax

    def loss_fn(params, collections, batch):
        logit = _apply(module, params, collections, batch)
        loss = jnp.mean(
            optax.sigmoid_binary_cross_entropy(
                logit.astype(jnp.float32), batch["label"].astype(jnp.float32)
            )
        )
        return loss, collections

    loss_fn.stateful = True
    # flag for parallel.train.make_train_step: training through the generic
    # optax path would leave the collection-resident tables frozen
    loss_fn.tables_frozen = True
    return loss_fn


def make_forward_fn(module, config: Config):
    import jax

    def forward(params, collections, batch):
        return jax.nn.sigmoid(_apply(module, params, collections, batch))

    forward.stateful = True
    return forward


def make_collection_shardings(config: Config, mesh):
    """Vocab-shard the embedding tables (and accumulators) over ``tp``.

    The capacity story for tables too large for one chip's HBM: with
    ``tp > 1`` each device stores ``1/tp`` of the fused table and its
    AdaGrad state (dim 0 = the vocab dim; ``DEFAULT_RULES`` maps the
    ``vocab`` logical axis to ``tp``).  Lookups on a vocab-sharded table
    partition as masked local gathers + psum under jit's global view; the
    dense update stays elementwise on the shards.  Returns ``None`` (fully
    replicated tables) when ``tp == 1`` or the bucket count doesn't divide.
    """
    import logging

    from tensorflowonspark_tpu.parallel import mesh as mesh_lib

    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    if tp <= 1:
        return None
    if config.total_buckets % tp:
        logging.getLogger(__name__).warning(
            "embedding tables will be REPLICATED on every device: "
            "total_buckets=%d does not divide tp=%d (the vocab-sharding "
            "capacity saving is lost; pick hash_buckets so 26*hash_buckets "
            "%% tp == 0)", config.total_buckets, tp,
        )
        return None
    vocab2d = mesh_lib.named_sharding(mesh, "tp", None)
    vocab1d = mesh_lib.named_sharding(mesh, "tp")
    return {
        "embedding": {"deep": vocab2d, "wide": vocab1d},
        "embedding_opt": {"deep_acc": vocab2d, "wide_acc": vocab1d},
    }


def make_sharded_train_step(module, config: Config, optimizer, mesh,
                            param_shardings, state, batch_example,
                            sequence_axes=None, collection_shardings=None):
    """The model-supplied train step the ``Trainer`` picks up.

    MLP tower: ``optimizer`` (optax) over ``state.params``.  Tables: AdaGrad
    at ``config.table_lr`` in the variant ``config.table_update`` names
    (``"dense"``: duplicates combined; ``"sparse"``: per occurrence — the
    module docstring).  ``"dense"`` runs on the looked-up rows alone or as
    a pass over the whole table, chosen when a batch shape is traced by
    :func:`update_touches_rows`; the returned step counts its calls by the
    same rule (``table_update_rows_steps_total`` /
    ``table_update_full_steps_total``).  Compiled through the same
    ``parallel.train.compile_step`` as the generic path (shardings, buffer
    donation — the table updates land in the donated buffers in place —
    and the active-mesh binding).
    """
    import jax
    import jax.numpy as jnp
    import optax

    from tensorflowonspark_tpu import embedding
    from tensorflowonspark_tpu.parallel import train as train_lib

    if config.table_update not in ("dense", "sparse"):
        raise ValueError(f"table_update must be dense|sparse, "
                         f"got {config.table_update!r}")
    sparse = config.table_update == "sparse"

    def _bce(logit, labels):
        return jnp.mean(
            optax.sigmoid_binary_cross_entropy(
                logit.astype(jnp.float32), labels.astype(jnp.float32)
            )
        )

    def _full_adagrad(table, acc, g, eps=1e-10):
        """Full-table AdaGrad pass; untouched rows see g == 0 and are
        unchanged, so the sparseness contract still holds bit-wise."""
        g = g.astype(jnp.float32)
        acc = acc + g * g
        update = (-config.table_lr * g * jax.lax.rsqrt(acc + eps))
        return table + update.astype(table.dtype), acc

    if collection_shardings is None:
        # direct callers (not via Trainer, which passes the hook's result)
        collection_shardings = make_collection_shardings(config, mesh)
    # the rows of a table one device passes over: 1/tp of them where the
    # tables are vocab-sharded
    tp = mesh.shape.get("tp", 1) if collection_shardings else 1
    rows_held = config.total_buckets // tp

    def _step(st, batch):
        emb = st.collections["embedding"]
        acc = st.collections["embedding_opt"]
        ids = fold_ids(batch["cat"], config)

        if sparse or update_touches_rows(rows_held, ids.size):
            # gradient w.r.t. the GATHERED rows: nothing of a table's shape.
            # The lookups are the forward pass's though they sit outside the
            # differentiated function, and the scope tells a profile so
            with jax.named_scope(train_lib.FORWARD_SCOPE):
                deep_rows = jnp.take(emb["deep"], ids, axis=0)
                wide_rows = jnp.take(emb["wide"], ids, axis=0)

            def loss_of(params, dr, wr):
                logit = _apply(module, params, st.collections, batch,
                               emb_rows=dr, wide_rows=wr)
                return _bce(logit, batch["label"])

            loss, (g_p, g_dr, g_wr) = jax.value_and_grad(
                loss_of, argnums=(0, 1, 2)
            )(st.params, deep_rows, wide_rows)
            if sparse:
                new_deep, new_dacc = embedding.sparse_adagrad_update(
                    emb["deep"], acc["deep_acc"], ids, g_dr, config.table_lr)
                new_wide, new_wacc = embedding.sparse_adagrad_update(
                    emb["wide"], acc["wide_acc"], ids, g_wr, config.table_lr)
            else:
                # one mask product sums both tables' duplicates: the wide
                # gradient rides as one more column beside the deep ones
                sums = embedding.sum_duplicate_grads(
                    ids, jnp.concatenate([g_dr, g_wr[..., None]], axis=-1))
                new_deep, new_dacc = embedding.adagrad_update_rows(
                    emb["deep"], acc["deep_acc"], ids, deep_rows,
                    sums[..., :-1], config.table_lr)
                new_wide, new_wacc = embedding.adagrad_update_rows(
                    emb["wide"], acc["wide_acc"], ids, wide_rows,
                    sums[..., -1], config.table_lr)
        else:
            def loss_of(params, deep, wide):
                dr = jnp.take(deep, ids, axis=0)
                wr = jnp.take(wide, ids, axis=0)
                logit = _apply(module, params, st.collections, batch,
                               emb_rows=dr, wide_rows=wr)
                return _bce(logit, batch["label"])

            loss, (g_p, g_deep, g_wide) = jax.value_and_grad(
                loss_of, argnums=(0, 1, 2)
            )(st.params, emb["deep"], emb["wide"])
            new_deep, new_dacc = _full_adagrad(
                emb["deep"], acc["deep_acc"], g_deep)
            new_wide, new_wacc = _full_adagrad(
                emb["wide"], acc["wide_acc"], g_wide)

        updates, opt_state = optimizer.update(g_p, st.opt_state, st.params)
        params = optax.apply_updates(st.params, updates)

        cols = {"embedding": {"deep": new_deep, "wide": new_wide},
                "embedding_opt": {"deep_acc": new_dacc,
                                  "wide_acc": new_wacc}}
        return train_lib.TrainState(params, opt_state, st.step + 1,
                                    cols), loss

    step = train_lib.compile_step(
        _step, mesh, param_shardings, state, batch_example,
        sequence_axes=sequence_axes,
        collection_shardings=collection_shardings,
    )
    return step if sparse else _CountedStep(step, rows_held)


class _CountedStep:
    """The compiled ``"dense"`` step, counting its calls by the execution
    :func:`update_touches_rows` gives the batch's shape — the rule the trace
    of that shape applied.  Everything else (``lower``, the jit's
    attributes) is the step's own."""

    def __init__(self, step, rows_held: int):
        self._step = step
        self._rows_held = rows_held
        # both registered at once, so the one that never counts reads 0
        self._counters = {
            True: obs.counter("table_update_rows_steps_total"),
            False: obs.counter("table_update_full_steps_total")}

    def __call__(self, state, batch):
        out = self._step(state, batch)
        self._counters[update_touches_rows(
            self._rows_held, batch["cat"].size)].inc()
        return out

    def __getattr__(self, name):
        return getattr(self._step, name)


def example_batch(config: Config, batch_size: int = 8, seed: int = 0):
    rng = np.random.RandomState(seed)
    return {
        "dense": rng.rand(batch_size, NUM_DENSE).astype(np.float32),
        "cat": rng.randint(
            0, config.hash_buckets, size=(batch_size, NUM_CAT)
        ).astype(np.int32),
        "label": rng.randint(0, 2, size=(batch_size,)).astype(np.int32),
    }
