"""Sparse embedding engine (`tensorflowonspark_tpu/embedding.py`): the
update must touch exactly the gathered rows and reproduce the documented
duplicate-id semantics (post-accumulation AdaGrad scaling)."""

import numpy as np
import pytest

import jax.numpy as jnp

from tensorflowonspark_tpu import embedding


def _dense_adagrad_reference(table, acc, ids, grad_rows, lr, eps=1e-10):
    """NumPy reference: scatter-add g^2, then scale every duplicate by the
    post-accumulation statistic (the semantics the module documents)."""
    table, acc = table.copy(), acc.copy()
    flat = ids.reshape(-1)
    g = grad_rows.reshape((flat.shape[0],) + table.shape[1:])
    np.add.at(acc, flat, g * g)
    for i, row in enumerate(flat):
        table[row] += -lr * g[i] / np.sqrt(acc[row] + eps)
    return table, acc


def test_adagrad_matches_reference_no_duplicates():
    rng = np.random.RandomState(0)
    table = rng.randn(32, 4).astype(np.float32)
    acc = np.abs(rng.randn(32, 4)).astype(np.float32)
    ids = rng.permutation(32)[:8].astype(np.int32)  # unique
    g = rng.randn(8, 4).astype(np.float32)

    new_t, new_a = embedding.sparse_adagrad_update(
        jnp.asarray(table), jnp.asarray(acc), jnp.asarray(ids),
        jnp.asarray(g), lr=0.1)
    ref_t, ref_a = _dense_adagrad_reference(table, acc, ids, g, lr=0.1)
    np.testing.assert_allclose(np.asarray(new_t), ref_t, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(new_a), ref_a, rtol=1e-5)


def test_adagrad_duplicate_ids_post_accumulation_semantics():
    rng = np.random.RandomState(1)
    table = rng.randn(8, 3).astype(np.float32)
    acc = np.zeros((8, 3), np.float32)
    ids = np.array([2, 2, 5], np.int32)  # row 2 hit twice
    g = rng.randn(3, 3).astype(np.float32)

    new_t, new_a = embedding.sparse_adagrad_update(
        jnp.asarray(table), jnp.asarray(acc), jnp.asarray(ids),
        jnp.asarray(g), lr=0.1)
    ref_t, ref_a = _dense_adagrad_reference(table, acc, ids, g, lr=0.1)
    np.testing.assert_allclose(np.asarray(new_a), ref_a, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(new_t), ref_t, rtol=1e-5)


def test_untouched_rows_bit_identical():
    rng = np.random.RandomState(2)
    table = rng.randn(64, 5).astype(np.float32)
    acc = np.abs(rng.randn(64, 5)).astype(np.float32)
    ids = np.array([[3, 9], [17, 3]], np.int32)  # multi-dim ids
    g = rng.randn(2, 2, 5).astype(np.float32)

    new_t, new_a = embedding.sparse_adagrad_update(
        jnp.asarray(table), jnp.asarray(acc), jnp.asarray(ids),
        jnp.asarray(g), lr=0.5)
    untouched = np.setdiff1d(np.arange(64), ids.reshape(-1))
    np.testing.assert_array_equal(np.asarray(new_t)[untouched],
                                  table[untouched])
    np.testing.assert_array_equal(np.asarray(new_a)[untouched],
                                  acc[untouched])
    touched = np.unique(ids.reshape(-1))
    assert not np.allclose(np.asarray(new_t)[touched], table[touched])


def test_scalar_row_table():
    """1-D table (the wide column): row shape is ()."""
    table = np.zeros(10, np.float32)
    acc = np.zeros(10, np.float32)
    ids = np.array([1, 1, 4], np.int32)
    g = np.array([1.0, 1.0, 2.0], np.float32)
    new_t, new_a = embedding.sparse_adagrad_update(
        jnp.asarray(table), jnp.asarray(acc), jnp.asarray(ids),
        jnp.asarray(g), lr=1.0)
    np.testing.assert_allclose(np.asarray(new_a),
                               [0, 2, 0, 0, 4, 0, 0, 0, 0, 0])
    # row 1: two dups each apply -1/sqrt(2); row 4: -2/sqrt(4)
    np.testing.assert_allclose(
        np.asarray(new_t)[[1, 4]], [-2 / np.sqrt(2), -1.0], rtol=1e-6)


def test_sparse_sgd_and_momentum_rejected():
    table = np.ones((6, 2), np.float32)
    ids = np.array([0, 5], np.int32)
    g = np.ones((2, 2), np.float32)
    new_t = embedding.sparse_sgd_update(
        jnp.asarray(table), jnp.asarray(ids), jnp.asarray(g), lr=0.5)
    np.testing.assert_allclose(np.asarray(new_t)[[0, 5]], 0.5)
    np.testing.assert_allclose(np.asarray(new_t)[1:5], 1.0)
    with pytest.raises(ValueError):
        embedding.sparse_sgd_update(
            jnp.asarray(table), jnp.asarray(ids), jnp.asarray(g),
            lr=0.5, momentum=0.9)


# ---------------------------------------------------------------------------
# Combined-duplicate AdaGrad on the looked-up rows alone (the default
# ``table_update="dense"`` executed without anything of the table's shape)
# ---------------------------------------------------------------------------


def _full_pass(table, acc, ids, grad_rows, lr, eps=1e-10):
    """What the full-table pass computes: the gather's VJP (duplicates
    summed into a table-shaped gradient), then AdaGrad over every row."""
    g = jnp.zeros(table.shape, jnp.float32).at[ids.reshape(-1)].add(
        grad_rows.reshape((-1,) + table.shape[1:]))
    acc = acc + g * g
    return table - lr * g / jnp.sqrt(acc + eps), acc


def _column_ids(kind, rng, batch, cols, per_col):
    """(batch, cols) ids whose columns never share an id, as ``fold_ids``
    leaves them."""
    if kind == "no_duplicates":
        local = np.stack([rng.permutation(per_col)[:batch]
                          for _ in range(cols)], axis=1)
    elif kind == "one_id":
        local = np.full((batch, cols), 3)
    else:  # zipf: a few hot ids a column, a long tail
        local = np.minimum(rng.zipf(1.3, (batch, cols)) - 1, per_col - 1)
    return (local + np.arange(cols) * per_col).astype(np.int32)


@pytest.mark.parametrize("row_shape", [(5,), ()], ids=["rows", "scalars"])
@pytest.mark.parametrize("kind", ["no_duplicates", "one_id", "zipf"])
def test_adagrad_update_rows_is_the_full_pass_on_the_touched_rows(kind,
                                                                  row_shape):
    rng = np.random.RandomState(3)
    batch, cols, per_col = 16, 4, 40
    table = rng.randn(cols * per_col, *row_shape).astype(np.float32)
    acc = np.abs(rng.randn(cols * per_col, *row_shape)).astype(np.float32)
    ids = _column_ids(kind, rng, batch, cols, per_col)
    g = rng.randn(batch, cols, *row_shape).astype(np.float32)

    g3 = jnp.asarray(g).reshape(batch, cols, -1)
    sums = embedding.sum_duplicate_grads(jnp.asarray(ids), g3).reshape(g.shape)
    new_t, new_a = embedding.adagrad_update_rows(
        jnp.asarray(table), jnp.asarray(acc), jnp.asarray(ids),
        jnp.asarray(table)[ids], sums, lr=0.1)
    ref_t, ref_a = _full_pass(jnp.asarray(table), jnp.asarray(acc),
                              jnp.asarray(ids), jnp.asarray(g), lr=0.1)
    np.testing.assert_allclose(np.asarray(new_t), np.asarray(ref_t),
                               rtol=2e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(new_a), np.asarray(ref_a),
                               rtol=2e-6, atol=1e-7)
    untouched = np.setdiff1d(np.arange(table.shape[0]), ids.reshape(-1))
    assert untouched.size > 0
    np.testing.assert_array_equal(np.asarray(new_t)[untouched],
                                  table[untouched])
    np.testing.assert_array_equal(np.asarray(new_a)[untouched],
                                  acc[untouched])


def test_sum_duplicate_grads_keeps_float32_sums():
    """A summand 2**-12 of its neighbour survives: the mask product runs at
    a precision that keeps float32 (one bfloat16 pass would drop it)."""
    ids = jnp.asarray(np.array([[7], [7], [9]], np.int32))
    g = jnp.asarray(np.array([[[1.0]], [[2.0 ** -12]], [[5.0]]], np.float32))
    sums = np.asarray(embedding.sum_duplicate_grads(ids, g))[:, 0, 0]
    np.testing.assert_array_equal(
        sums, np.array([1.0 + 2.0 ** -12, 1.0 + 2.0 ** -12, 5.0], np.float32))
