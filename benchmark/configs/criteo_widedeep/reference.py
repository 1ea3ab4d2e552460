"""Plain reference of the ``criteo_widedeep`` configuration: weights from the
seed, forward pass, loss, gradients, AdaGrad on the tables and AdamW on the
MLP, in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.

It imports nothing of the program and takes nothing the program has made.
Model (Cheng et al.; the upstream example's layout): 26 categorical ids a
row, each looked up in its feature's own table of ``hash_buckets`` rows — a
32-wide embedding for the deep tower and one weight for the wide part; the
deep tower is an MLP over the 26 embeddings and ``log1p(max(dense, 0))`` of
the 13 dense features; logit = sum of wide weights + MLP output; mean
sigmoid cross-entropy.  Update: AdaGrad without initial accumulator on both
tables, gradients of duplicate ids in a batch summed before they are squared
(the gradient of a gather); AdamW on the MLP.

The tables are kept a feature at a time (``deep/f07``), which is how the
output check compares them, leaf by leaf.

``lower`` names a control's precision.  ``"float8"`` (this configuration's
control): the operands of every matrix product of the MLP are rounded to
``float8_e4m3fn`` before the product, and the gradients flowing back
through those casts with them — the precision below the one the
program multiplies in on a TPU, where a float32 product at JAX's default
precision is one bfloat16 pass (PERF.md, PR 23).  ``"bfloat16"``: every
stored value — table rows, accumulators, MLP parameters, activations — is
rounded to bfloat16 where it is produced; read on the chip, it cannot be
told from the program for exactly that reason, and is kept for the record.
"""

from __future__ import annotations

import functools

NUM_DENSE = 13
NUM_CAT = 26
DEEP_STD = 0.01


def mlp_dims(config: dict) -> list:
    return [NUM_CAT * config["embed_dim"] + NUM_DENSE, *config["hidden"], 1]


def feature_names(kind: str) -> list:
    return [f"{kind}/f{f:02d}" for f in range(NUM_CAT)]


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


@functools.lru_cache(maxsize=None)
def _deep_leaf_fn(buckets: int, embed: int):
    """One compiled maker for all 26 embedding leaves of a size."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda key, f: DEEP_STD * jax.random.normal(
        jax.random.fold_in(key, f), (buckets, embed), jnp.float32))


def make_weights(config: dict, seed: int, only=None) -> dict:
    """All parameters, float32, on the device, in one jitted call.  ``only``
    (a collection of leaf names) makes just those leaves again — every leaf
    is drawn from its own key."""
    import jax
    import jax.numpy as jnp

    buckets, embed = config["hash_buckets"], config["embed_dim"]
    dims = mlp_dims(config)

    def build(key):
        out = {}
        for f, name in enumerate(feature_names("deep")):
            if only is None or name in only:
                out[name] = DEEP_STD * jax.random.normal(
                    jax.random.fold_in(key, f), (buckets, embed),
                    jnp.float32)
        for name in feature_names("wide"):
            if only is None or name in only:
                out[name] = jnp.zeros((buckets,), jnp.float32)
        for i, (a, b) in enumerate(zip(dims, dims[1:])):
            gain = 1.0 if i == len(dims) - 2 else 2.0
            k, bias = f"mlp{i}/kernel", f"mlp{i}/bias"
            if only is None or k in only:
                out[k] = jax.random.normal(
                    jax.random.fold_in(key, 1000 + i), (a, b),
                    jnp.float32) * (gain / a) ** 0.5
            if only is None or bias in only:
                out[bias] = jnp.zeros((b,), jnp.float32)
        return out

    return jax.jit(build)(seed_key(seed))


def make_leaf(config: dict, seed: int, name: str):
    """One leaf of :func:`make_weights` made again, alone."""
    import jax.numpy as jnp

    kind, _, feature = name.partition("/f")
    if kind == "deep":
        return _deep_leaf_fn(config["hash_buckets"], config["embed_dim"])(
            seed_key(seed), int(feature))
    if kind == "wide":
        return jnp.zeros((config["hash_buckets"],), jnp.float32)
    return make_weights(config, seed, only=(name,))[name]


def _rounders(lower):
    """``(store, operand)``: what rounds a stored value and what rounds an
    operand of a matrix product under the control ``lower``.  Plain casts
    both ways: their derivative casts the gradient the same way, so the
    control's backward pass is in that precision too."""
    import jax.numpy as jnp

    def through(dtype):
        return lambda a: a.astype(dtype).astype(jnp.float32)

    same = lambda a: a  # noqa: E731
    if lower is None:
        return same, same
    if lower == "bfloat16":
        return through(jnp.bfloat16), same
    if lower == "float8":
        return same, through(jnp.float8_e4m3fn)
    raise ValueError(f"unknown lower precision {lower!r}")


def loss_fn(weights: dict, batch: dict, config: dict, lower=None):
    import jax
    import jax.numpy as jnp

    rnd, operand = _rounders(lower)
    cat = batch["cat"]
    deep = [rnd(jnp.take(rnd(weights[n]), cat[:, f], axis=0))
            for f, n in enumerate(feature_names("deep"))]
    wide = [jnp.take(rnd(weights[n]), cat[:, f], axis=0)
            for f, n in enumerate(feature_names("wide"))]
    wide_logit = rnd(sum(wide))
    x = jnp.concatenate(
        deep + [rnd(jnp.log1p(jnp.maximum(batch["dense"], 0.0)))], axis=-1)
    n_layers = len(mlp_dims(config)) - 1
    for i in range(n_layers):
        x = jnp.dot(operand(x), operand(rnd(weights[f"mlp{i}/kernel"])),
                    precision=jax.lax.Precision.HIGHEST)
        x = rnd(x + rnd(weights[f"mlp{i}/bias"]))
        if i < n_layers - 1:
            x = jax.nn.relu(x)
    logit = wide_logit + x[:, 0]
    y = batch["label"].astype(jnp.float32)
    # sigmoid cross-entropy, the numerically stable form
    per_row = jnp.maximum(logit, 0.0) - logit * y + jnp.log1p(
        jnp.exp(-jnp.abs(logit)))
    return per_row.mean()


def init_state(weights: dict) -> dict:
    import jax.numpy as jnp

    def zeros(keep):
        # a buffer of its own for every leaf: the update donates them
        return {k: jnp.zeros(v.shape, v.dtype) + 0.0
                for k, v in weights.items() if keep(k)}

    return {"acc": zeros(lambda k: "/f" in k),
            "mu": zeros(lambda k: k.startswith("mlp")),
            "nu": zeros(lambda k: k.startswith("mlp")),
            "count": jnp.zeros((), jnp.int32)}


def update(weights: dict, grads: dict, state: dict, config: dict,
           lower=None):
    import jax
    import jax.numpy as jnp

    rnd, _ = _rounders(lower)
    opt, table = config["optimizer"], config["table_optimizer"]
    b1, b2 = opt["b1"], opt["b2"]
    count = state["count"] + 1
    t = count.astype(jnp.float32)
    new_w, acc, mu, nu = {}, {}, {}, {}
    for k, g in grads.items():
        if k.startswith("mlp"):
            mu[k] = rnd(b1 * state["mu"][k] + (1.0 - b1) * g)
            nu[k] = rnd(b2 * state["nu"][k] + (1.0 - b2) * g * g)
            m_hat = mu[k] / (1.0 - b1 ** t)
            v_hat = nu[k] / (1.0 - b2 ** t)
            step = (m_hat / (jnp.sqrt(v_hat) + opt["eps"])
                    + opt["weight_decay"] * weights[k])
            new_w[k] = rnd(weights[k] - opt["learning_rate"] * step)
        else:
            acc[k] = rnd(state["acc"][k] + g * g)
            new_w[k] = rnd(weights[k] - table["learning_rate"] * g
                           * jax.lax.rsqrt(acc[k] + table["eps"]))
    return new_w, {"acc": acc, "mu": mu, "nu": nu, "count": count}


def leaf_norms(tree: dict) -> dict:
    import jax.numpy as jnp

    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for k, v in tree.items()}


def follow(config: dict, seed: int, batches: list, lower=None) -> dict:
    """Follow the first ``len(batches)`` training steps from the seeded
    weights: each step's loss, the per-leaf norm of the first gradient, and
    the per-leaf norm of the parameters' change over all the steps."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        rnd, _ = _rounders(lower)
        weights = {k: rnd(v) for k, v in make_weights(config, seed).items()}
        state = init_state(weights)
        step = jax.jit(jax.value_and_grad(
            lambda w, b: loss_fn(w, b, config, lower)))
        apply = jax.jit(lambda w, g, s: update(w, g, s, config, lower),
                        donate_argnums=(0, 2))
        losses, grad_norms = [], None
        for batch in batches:
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            loss, grads = step(weights, batch)
            if grad_norms is None:
                grad_norms = leaf_norms(grads)
            losses.append(float(loss))
            weights, state = apply(weights, grads, state)
            del grads
        del state
        change = {}
        for name in list(weights):
            first = make_leaf(config, seed, name)
            change[name] = weights.pop(name) - rnd(first)
        return {"losses": losses, "grad_norms": grad_norms,
                "change_norms": leaf_norms(change)}
