"""Sparse embedding engine: update only the rows a step actually touched.

Reference anchor: the reference delegates embedding training to TensorFlow,
whose sparse path (``tf.nn.embedding_lookup_sparse`` gradients as
``IndexedSlices``, and on TPU the TPUEmbedding engine) applies optimizer
updates only to the gathered rows.  An optax-style *dense* update instead
touches every parameter every step: for wide&deep's fused 86M-parameter
table that is ~2.4 GB of HBM traffic per step (grad materialization +
p/m/v read-modify-write), which measured as the steps/sec bound on a v5e
chip (``BENCH_NOTES.md``).

The TPU-native equivalent here keeps the tables out of the optax parameter
tree and applies the optimizer with gather/scatter on exactly the looked-up
ids — O(batch·features·dim) HBM traffic instead of O(vocab·dim).  All ops
are static-shaped ``.at[].add`` scatters and gathers, so the whole update
jits into the train step and runs in-place on the donated table buffers.

Duplicate-id semantics (two examples in the batch hit the same row): the
squared gradients of all duplicates are accumulated FIRST (one scatter-add),
then every duplicate's update is scaled by the post-accumulation statistic —
the same "apply the summed slice" convention TF's sparse AdaGrad kernels
use, and exactly reproducible: see ``tests/test_embedding.py``.

Two AdaGrad variants live here.  :func:`sparse_adagrad_update` is the
per-occurrence one above (wide&deep's ``table_update="sparse"``).
:func:`sum_duplicate_grads` + :func:`adagrad_update_rows` are the combined
one — the gradients of an id's occurrences are summed BEFORE they are
squared, which is what a gather's VJP followed by a pass over the whole
table computes — run on the looked-up rows alone.  Wide&deep's default
(``"dense"``) takes it wherever the table is large for the batch
(``models/widedeep.py::update_touches_rows``).

Multi-chip note: tables live replicated (one copy per device, the default
sharding for non-param collections in ``parallel.train.state_shardings``);
under ``jit``'s global-view semantics the scatter is a single global op, so
XLA keeps replicas consistent by combining each data shard's updates.
Vocab-sharded tables (EP-style, for tables too large for one device's HBM)
are the designed extension point: shard the ``vocab`` dim of table and
accumulator alike and the same global-view scatter partitions over it.
"""

from __future__ import annotations


def sparse_adagrad_update(table, acc, ids, grad_rows, lr: float,
                          eps: float = 1e-10):
    """One AdaGrad step on only the gathered rows of ``table``.

    ``table``: ``(vocab, *row)`` parameter array; ``acc``: same-shape float32
    accumulator; ``ids``: integer array of any shape; ``grad_rows``: the loss
    gradient w.r.t. ``table[ids]``, shape ``ids.shape + row``.

    Returns ``(new_table, new_acc)``.  Rows not in ``ids`` are bit-identical
    to their inputs — the sparseness contract.
    """
    import jax.numpy as jnp
    from jax import lax

    row_shape = table.shape[1:]
    flat_ids = ids.reshape(-1)
    g = grad_rows.reshape((flat_ids.shape[0],) + row_shape).astype(jnp.float32)

    acc = acc.at[flat_ids].add(g * g)
    # gather AFTER the add: duplicates all see the fully-accumulated value
    scale = lax.rsqrt(acc[flat_ids] + eps)
    update = (-lr * g * scale).astype(table.dtype)
    return table.at[flat_ids].add(update), acc


def sum_duplicate_grads(ids, grad_rows):
    """Replace each occurrence's gradient by the sum over every occurrence
    of its id in the same column: the value the gather's VJP leaves in that
    id's row of a table-shaped gradient, without that gradient.

    ``ids``: ``(B, F)`` integers whose columns never share an id (wide&deep's
    ``fold_ids`` gives every feature a range of its own), so duplicates are
    ``F`` separate problems of ``B`` ids; ``grad_rows``: ``(B, F, E)``.
    One ``(B, B)`` equality mask a column times that column's gradients:
    ``F * B * B * E`` multiply-adds at ``HIGHEST`` precision (the mask is
    exact and the sums stay float32; one bfloat16 pass would round the
    gradients).  On a v5e chip the compiler builds the mask inside the
    product and never stores it: 0.06 ms at B = 1,024, 0.98 ms at 4,096
    (PR 30's chip runs).  One mask over all ``B * F`` ids would have ``F``
    times the elements for the same sums.
    """
    import jax
    import jax.numpy as jnp

    cols = ids.T  # (F, B)
    same = (cols[:, :, None] == cols[:, None, :]).astype(jnp.float32)
    return jnp.einsum("fij,jfe->ife", same, grad_rows.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def adagrad_update_rows(table, acc, ids, rows, grad_sums, lr: float,
                        eps: float = 1e-10):
    """Combined-duplicate AdaGrad that reads and writes only ``ids``' rows.

    The arithmetic of a pass over the whole table with the gather's VJP as
    its gradient — ``acc += g * g; row -= lr * g * rsqrt(acc + eps)``, ``g``
    the SUM over an id's occurrences, all float32 — on the looked-up rows
    alone.  ``rows`` is ``table[ids]`` (the forward pass has it) and
    ``grad_sums`` comes from :func:`sum_duplicate_grads`, so every
    occurrence of an id computes the same new row and the two scatters
    *set*: duplicates write one value, in whatever order.  No other row is
    read or written, and nothing of the table's shape is allocated.
    Contrast :func:`sparse_adagrad_update`, which squares each occurrence on
    its own: another trajectory.

    Returns ``(new_table, new_acc)``.
    """
    import jax.numpy as jnp
    from jax import lax

    g = grad_sums.astype(jnp.float32)
    acc_rows = jnp.take(acc, ids, axis=0) + g * g
    update = -lr * g * lax.rsqrt(acc_rows + eps)
    new_rows = rows + update.astype(table.dtype)
    return table.at[ids].set(new_rows), acc.at[ids].set(acc_rows)


def sparse_sgd_update(table, ids, grad_rows, lr: float, momentum=None):
    """Plain sparse SGD on the gathered rows (no per-row state).

    Returns ``new_table``.  ``momentum`` is deliberately unsupported —
    momentum is a *dense* statistic (it decays rows the step never touched),
    so a sparse variant would silently change the algorithm; use
    :func:`sparse_adagrad_update` when per-row state is wanted.
    """
    import jax.numpy as jnp

    if momentum is not None:
        raise ValueError("momentum is a dense statistic; sparse SGD "
                         "supports none (see docstring)")
    row_shape = table.shape[1:]
    flat_ids = ids.reshape(-1)
    g = grad_rows.reshape((flat_ids.shape[0],) + row_shape).astype(jnp.float32)
    return table.at[flat_ids].add((-lr * g).astype(table.dtype))
