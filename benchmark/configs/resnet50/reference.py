"""Plain reference of the ``resnet50`` configuration: weights from the seed,
forward pass, loss, gradients and AdamW, in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.

It imports nothing of the program and takes nothing the program has made.
The network is He et al.'s 50-layer bottleneck ResNet with the stride in the
3x3 convolution (v1.5) and GroupNorm (32 groups, eps 1e-6, statistics over
height, width and the channels of a group, per example) where the paper has
BatchNorm; global average pool; a dense classifier; mean softmax
cross-entropy.  Departure from the program: every activation stays float32
(the program computes convolutions and normalisation outputs in bfloat16).

``lower`` names the control's precision (``"float8"`` for this bfloat16
configuration): the operands of every convolution and of the classifier are
cast to ``float8_e4m3fn`` and back before the product, and so are the
gradients that flow back through those casts: the products computed in the
next precision down, forward and backward.  (A milder control, rounding the
forward operands only, was read on the chip too and is NOT told from the
program by the numbers compared: PERF.md, PR 23.)
"""

from __future__ import annotations

import functools

GN_EPS = 1e-6


def leaf_shapes(config: dict) -> dict:
    """Name -> (shape, fan_in or None) of every parameter, forward order.
    ``fan_in`` None marks a normalisation scale (ones) or a bias (zeros)."""
    width, out = config["width"], {}

    def conv(name, k, c_in, c_out):
        out[name] = ((k, k, c_in, c_out), k * k * c_in)

    def norm(name, ch):
        out[name + "/scale"] = ((ch,), None)
        out[name + "/bias"] = ((ch,), None)

    conv("stem/conv", 7, 3, width)
    norm("stem/norm", width)
    c_in = width
    for stage, blocks in enumerate(config["stage_sizes"]):
        filters = width * 2 ** stage
        for block in range(blocks):
            p = f"s{stage}b{block}"
            conv(p + "/conv1", 1, c_in, filters)
            norm(p + "/norm1", filters)
            conv(p + "/conv2", 3, filters, filters)
            norm(p + "/norm2", filters)
            conv(p + "/conv3", 1, filters, 4 * filters)
            norm(p + "/norm3", 4 * filters)
            if c_in != 4 * filters or (stage > 0 and block == 0):
                conv(p + "/proj", 1, c_in, 4 * filters)
                norm(p + "/projnorm", 4 * filters)
            c_in = 4 * filters
    out["head/kernel"] = ((c_in, config["num_classes"]), c_in)
    out["head/bias"] = ((config["num_classes"],), None)
    return out


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make_weights(config: dict, seed: int) -> dict:
    """All parameters, float32, on the device, in one jitted call: He-normal
    convolution kernels (std sqrt(2 / fan_in)), a LeCun-normal classifier
    (std sqrt(1 / fan_in)), unit scales, zero biases."""
    import jax
    import jax.numpy as jnp

    shapes = leaf_shapes(config)

    def build(key):
        weights = {}
        for i, (name, (shape, fan_in)) in enumerate(shapes.items()):
            if fan_in is None:
                fill = 1.0 if name.endswith("/scale") else 0.0
                weights[name] = jnp.full(shape, fill, jnp.float32)
            else:
                gain = 1.0 if name == "head/kernel" else 2.0
                weights[name] = (jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                    * (gain / fan_in) ** 0.5)
        return weights

    return jax.jit(build)(seed_key(seed))


def _rounder(lower):
    """Round to the control's precision and back.  A plain cast both ways:
    its derivative casts the gradient the same way, so the control's
    backward pass is in that precision too — what "computed in float8"
    means, with no loss scaling and nothing kept in float32."""
    import jax.numpy as jnp

    if lower is None:
        return lambda a: a
    kinds = {"float8": jnp.float8_e4m3fn, "bfloat16": jnp.bfloat16}
    if lower not in kinds:
        raise ValueError(f"unknown lower precision {lower!r}")
    return lambda a: a.astype(kinds[lower]).astype(jnp.float32)


def forward(weights: dict, images, config: dict, lower=None):
    """Logits (float32) of ``images`` (N, side, side, 3) float32."""
    import jax
    import jax.numpy as jnp

    rnd = _rounder(lower)

    def conv(x, name, stride=1):
        return jax.lax.conv_general_dilated(
            rnd(x), rnd(weights[name]), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)

    def norm(x, name):
        n, h, w, c = x.shape
        groups = min(config["groups"], c)
        g = x.reshape(n, h, w, groups, c // groups)
        mean = g.mean(axis=(1, 2, 4), keepdims=True)
        var = ((g - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
        g = (g - mean) * jax.lax.rsqrt(var + GN_EPS)
        return (g.reshape(n, h, w, c) * weights[name + "/scale"]
                + weights[name + "/bias"])

    x = jax.nn.relu(norm(conv(images, "stem/conv", 2), "stem/norm"))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for stage, blocks in enumerate(config["stage_sizes"]):
        for block in range(blocks):
            p = f"s{stage}b{block}"
            stride = 2 if stage > 0 and block == 0 else 1
            y = jax.nn.relu(norm(conv(x, p + "/conv1"), p + "/norm1"))
            y = jax.nn.relu(norm(conv(y, p + "/conv2", stride), p + "/norm2"))
            y = norm(conv(y, p + "/conv3"), p + "/norm3")
            if p + "/proj" in weights:
                x = norm(conv(x, p + "/proj", stride), p + "/projnorm")
            x = jax.nn.relu(x + y)
    x = x.mean(axis=(1, 2))
    return (jnp.dot(rnd(x), rnd(weights["head/kernel"]),
                    precision=jax.lax.Precision.HIGHEST)
            + weights["head/bias"])


def loss_sum(weights: dict, batch: dict, config: dict, lower=None):
    """Sum (not mean) of the rows' softmax cross-entropy."""
    import jax
    import jax.numpy as jnp

    logits = forward(weights, batch["image"], config, lower)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    picked = jnp.take_along_axis(logp, batch["label"][:, None], axis=1)
    return -picked.sum()


def loss_and_grads(weights: dict, batch: dict, config: dict, lower=None,
                   block_rows: int = 32):
    """Mean loss of the batch and its gradient, computed in blocks of rows
    (GroupNorm keeps no statistics across examples, so the batch's gradient
    is the sum of its blocks') so that float32 activations of the whole
    batch need never be held at once."""
    import jax
    import jax.numpy as jnp

    n = batch["label"].shape[0]
    block_rows = min(block_rows, n)
    if n % block_rows:
        raise ValueError(f"batch {n} is not a multiple of {block_rows}")
    fn = _block_fn(_freeze(config), lower)
    total, grads = jnp.float32(0.0), None
    for start in range(0, n, block_rows):
        block = {k: v[start:start + block_rows] for k, v in batch.items()}
        part, g = fn(weights, block)
        total = total + part
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    scale = 1.0 / n
    return total * scale, jax.tree_util.tree_map(lambda a: a * scale, grads)


def _freeze(config: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in config.items()
                        if isinstance(v, (int, float, str, list))))


@functools.lru_cache(maxsize=8)
def _block_fn(frozen_config, lower):
    import jax

    config = {k: list(v) if isinstance(v, tuple) else v
              for k, v in frozen_config}
    return jax.jit(jax.value_and_grad(
        lambda w, b: loss_sum(w, b, config, lower)))


def adamw_init(weights: dict) -> dict:
    import jax
    import jax.numpy as jnp

    zeros = jax.tree_util.tree_map(jnp.zeros_like, weights)
    return {"mu": zeros, "nu": zeros, "count": 0}


def adamw_update(weights: dict, grads: dict, state: dict, opt: dict):
    """One AdamW step as the optimizer the configuration names defines it
    (bias-corrected moments; decoupled weight decay added before the
    learning rate is applied)."""
    import jax.numpy as jnp

    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    count = state["count"] + 1
    new_w, mu, nu = {}, {}, {}
    for k, g in grads.items():
        mu[k] = b1 * state["mu"][k] + (1.0 - b1) * g
        nu[k] = b2 * state["nu"][k] + (1.0 - b2) * g * g
        m_hat = mu[k] / (1.0 - b1 ** count)
        v_hat = nu[k] / (1.0 - b2 ** count)
        step = m_hat / (jnp.sqrt(v_hat) + eps) + opt["weight_decay"] * weights[k]
        new_w[k] = weights[k] - opt["learning_rate"] * step
    return new_w, {"mu": mu, "nu": nu, "count": count}


def leaf_norms(tree: dict) -> dict:
    import jax.numpy as jnp

    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for k, v in tree.items()}


def follow(config: dict, seed: int, batches: list, lower=None) -> dict:
    """Follow the first ``len(batches)`` training steps from the seeded
    weights.  Returns each step's loss, the per-leaf norm of the first
    gradient, and the per-leaf norm of the parameters' change over all the
    steps — the numbers the program's are compared with."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        first = make_weights(config, seed)
        weights, state = first, adamw_init(first)
        losses, grad_norms = [], None
        for batch in batches:
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            loss, grads = loss_and_grads(weights, batch, config, lower)
            if grad_norms is None:
                grad_norms = leaf_norms(grads)
            losses.append(float(loss))
            weights, state = adamw_update(weights, grads, state,
                                          config["optimizer"])
        change = {k: weights[k] - first[k] for k in first}
        return {"losses": losses, "grad_norms": grad_norms,
                "change_norms": leaf_norms(change)}
