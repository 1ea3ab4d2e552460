"""Plain reference of the ``granite_4_0_h_micro`` configuration: weights from
the seed, forward pass, loss, gradients and AdamW, in float32 ``jax.numpy``
under ``jax.default_matmul_precision("highest")``.

It imports nothing of the program and takes nothing the program has made.
The model is IBM's Granite-4.0-H-Micro (``config.json`` of
``ibm-granite/granite-4.0-h-micro``, ``model_type`` ``granitemoehybrid`` with
no routed experts; the state-space mixer is Mamba-2's, Dao & Gu,
arXiv:2405.21060), on packed rows: tokens ``u`` with segment ids ``s``, the
document's number inside the row.

    x = 12 E[u];  per layer  x += 0.22 mixer(rms(x));  x += 0.22 mlp(rms(x))
    logits = rms(x) E^T / 8;  mlp(h) = W_down (silu(h W_gate) * (h W_up))
    mamba:  [z | xBC | dt] = h W_in;  xBC = silu(conv(xBC) + b), a tap that
            would reach another document reads zero;  [x | B | C] = xBC;
            dt = softplus(dt + dt_bias);  A = -exp(A_log);
            S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, S = 0 entering a
            document's first token;  y_t = S_t C_t + D x_t;
            out = rms_w(y * silu(z)) W_out
    attention (layer 5): 32 query heads on 8 key/value heads of 64, no
            positional encoding, scores * 1/64, mask j <= i and s_j == s_i
    loss:   mean cross-entropy of logits_t against u_{t+1} over the positions
            with s_{t+1} == s_t

The recurrence is computed as it is written, a ``lax.scan`` over tokens (in
blocks that are recomputed in the backward pass, which changes no number);
attention a block of queries at a time; a training step a layer at a time,
the layer's forward redone for its gradient and AdamW applied to the layer
at once, so that weights and both moments (12 bytes a parameter) and one
layer's float32 working set fit a chip.

Departures from the published model: depth (layers 0-9 of 40, one period),
vocabulary (the first ``vocab_size`` rows of 100,352: ids, logits and loss
are over the slice), random weights from the seed (Mamba-2's published
initialisation, ``assumed`` in the configuration's file), no
``time_step_limit`` clamp.  Departure from the program: every activation
stays float32 (the program's are bfloat16).

``lower`` names the control's precision (``"float8"`` for this bfloat16
configuration): the operands of every matrix product — the projections, the
feed-forward, attention's scores and values, the head, and the recurrence's
``x``, ``B`` and ``C`` — are cast to ``float8_e4m3fn`` and back before the
product, and so are the gradients that flow back through those casts.
"""

from __future__ import annotations

import functools
import math

SCAN_BLOCK = 128        # tokens of the recurrence recomputed together
QUERY_BLOCK = 256


def layer_types(config: dict) -> list:
    return list(config["layer_types"][:config["num_hidden_layers"]])


def leaf_shapes(config: dict) -> dict:
    """Name -> (shape, kind) of every parameter, forward order.  ``kind``
    names the leaf's initialisation."""
    d, f = config["hidden_size"], config["shared_intermediate_size"]
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    conv_dim = heads * p + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    kv = config["num_key_value_heads"] * (d // config["num_attention_heads"])
    out = {"embed": ((config["vocab_size"], d), "normal")}
    for i, kind in enumerate(layer_types(config)):
        pre = f"l{i:02d}/"
        out[pre + "norm1"] = ((d,), "ones")
        if kind == "mamba":
            out[pre + "in_proj"] = ((d, heads * p + conv_dim + heads), "normal")
            out[pre + "conv_w"] = ((config["mamba_d_conv"], conv_dim), "conv")
            out[pre + "conv_b"] = ((conv_dim,), "conv")
            out[pre + "dt_bias"] = ((heads,), "dt_bias")
            out[pre + "A_log"] = ((heads,), "a_log")
            out[pre + "D"] = ((heads,), "ones")
            out[pre + "gate_norm"] = ((heads * p,), "ones")
            out[pre + "out_proj"] = ((heads * p, d), "normal")
        else:
            out[pre + "wq"] = ((d, d), "normal")
            out[pre + "wk"] = ((d, kv), "normal")
            out[pre + "wv"] = ((d, kv), "normal")
            out[pre + "wo"] = ((d, d), "normal")
        out[pre + "norm2"] = ((d,), "ones")
        out[pre + "mlp_gate"] = ((d, f), "normal")
        out[pre + "mlp_up"] = ((d, f), "normal")
        out[pre + "mlp_down"] = ((f, d), "normal")
    out["final_norm"] = ((d,), "ones")
    return out


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


@functools.lru_cache(maxsize=None)
def _leaf_fn(shape: tuple, kind: str, taps: int):
    """One compiled maker for every leaf of a shape and kind."""
    import jax
    import jax.numpy as jnp

    def make(key, index):
        key = jax.random.fold_in(key, index)
        if kind == "ones":
            return jnp.ones(shape, jnp.float32)
        if kind == "normal":
            return 0.02 * jax.random.normal(key, shape, jnp.float32)
        if kind == "a_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0))
        if kind == "dt_bias":       # softplus(dt_bias) is log-uniform
            dt = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        bound = 1.0 / math.sqrt(taps)   # "conv": PyTorch's Conv1d default
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)

    return jax.jit(make)


def make_leaf(config: dict, seed: int, name: str):
    """One parameter, float32, on the device: every leaf is drawn from its
    own key, so any can be made again without the rest."""
    shapes = leaf_shapes(config)
    shape, kind = shapes[name]
    return _leaf_fn(tuple(shape), kind, config["mamba_d_conv"])(
        seed_key(seed), list(shapes).index(name))


def make_weights(config: dict, seed: int) -> dict:
    return {name: make_leaf(config, seed, name)
            for name in leaf_shapes(config)}


def _rounder(lower):
    """Round to the control's precision and back.  A plain cast both ways:
    its derivative casts the gradient the same way."""
    import jax.numpy as jnp

    if lower is None:
        return lambda a: a
    kinds = {"float8": jnp.float8_e4m3fn, "bfloat16": jnp.bfloat16}
    if lower not in kinds:
        raise ValueError(f"unknown lower precision {lower!r}")
    return lambda a: a.astype(kinds[lower]).astype(jnp.float32)


def _dot(spec, a, b, rnd):
    import jax
    import jax.numpy as jnp

    return jnp.einsum(spec, rnd(a), rnd(b),
                      precision=jax.lax.Precision.HIGHEST)


def rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def conv(xbc, w, b, seg):
    """``y_t = b + sum_{j<K} w[K-1-j] x_{t-j}``, a tap outside the row or
    in another document reading zero.  ``xbc`` (T, C), ``w`` (K, C)."""
    import jax.numpy as jnp

    taps, t = w.shape[0], xbc.shape[0]
    at = jnp.arange(t)
    y = jnp.zeros_like(xbc) + b
    for j in range(taps):
        src = at - j
        ok = (src >= 0) & (seg[jnp.maximum(src, 0)] == seg)
        y = y + jnp.where(ok[:, None], xbc[jnp.maximum(src, 0)], 0.0) \
            * w[taps - 1 - j]
    return y


def recurrence(x, dt, a, b, c, seg):
    """The recurrence as written, token by token.  ``x`` (T, H, P), ``dt``
    (T, H), ``a`` (H,), ``b`` and ``c`` (T, G, N); returns ``S_t C_t``
    (T, H, P).  A head uses the ``B`` and ``C`` of its group."""
    import jax
    import jax.numpy as jnp

    t, heads, p = x.shape
    rep = heads // b.shape[1]
    first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])

    def token(state, inp):
        x_t, dt_t, b_t, c_t, first_t = inp
        state = jnp.where(first_t, 0.0, state)
        b_h, c_h = jnp.repeat(b_t, rep, axis=0), jnp.repeat(c_t, rep, axis=0)
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        return state, jnp.sum(state * c_h[:, None, :], axis=-1)

    size = next(s for s in range(min(SCAN_BLOCK, t), 0, -1) if t % s == 0)

    def block(state, inps):
        return jax.lax.scan(token, state, inps)

    blocks = jax.tree_util.tree_map(
        lambda v: v.reshape(t // size, size, *v.shape[1:]),
        (x, dt, b, c, first))
    _, y = jax.lax.scan(jax.checkpoint(block),
                        jnp.zeros((heads, p, b.shape[2]), jnp.float32), blocks)
    return y.reshape(t, heads, p)


def mamba(w, h, seg, config, rnd):
    import jax
    import jax.numpy as jnp

    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    groups, n = config["mamba_n_groups"], config["mamba_d_state"]
    d_inner, t = heads * p, h.shape[0]
    zxbcdt = _dot("td,de->te", h, w["in_proj"], rnd)
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner:2 * d_inner + 2 * groups * n]
    dt = zxbcdt[:, 2 * d_inner + 2 * groups * n:]
    xbc = jax.nn.silu(conv(xbc, w["conv_w"], w["conv_b"], seg))
    x = xbc[:, :d_inner].reshape(t, heads, p)
    b = xbc[:, d_inner:d_inner + groups * n].reshape(t, groups, n)
    c = xbc[:, d_inner + groups * n:].reshape(t, groups, n)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y = recurrence(rnd(x), dt, -jnp.exp(w["A_log"]), rnd(b), rnd(c), seg)
    y = y + w["D"][:, None] * x
    y = (y.reshape(t, d_inner) * jax.nn.silu(z)).reshape(
        t, groups, d_inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + config["rms_norm_eps"])
    return _dot("te,ed->td", y.reshape(t, d_inner) * w["gate_norm"],
                w["out_proj"], rnd)


def attention(w, h, seg, config, rnd):
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, rep = config["hidden_size"] // heads, heads // kv
    q = _dot("td,de->te", h, w["wq"], rnd).reshape(t, kv, rep, hd)
    k = _dot("td,de->te", h, w["wk"], rnd).reshape(t, kv, hd)
    v = _dot("td,de->te", h, w["wv"], rnd).reshape(t, kv, hd)
    at = jnp.arange(t)
    size = next(s for s in range(min(QUERY_BLOCK, t), 0, -1) if t % s == 0)

    def block(args):
        qb, sb, ab = args
        s = _dot("ikrd,jkd->krij", qb, k, rnd) * config["attention_multiplier"]
        mask = (ab[:, None] >= at[None, :]) & (sb[:, None] == seg[None, :])
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return _dot("krij,jkd->ikrd", p, v, rnd)

    o = jax.lax.map(jax.checkpoint(block), (
        q.reshape(t // size, size, kv, rep, hd), seg.reshape(-1, size),
        at.reshape(-1, size)))
    return _dot("te,ed->td", o.reshape(t, heads * hd), w["wo"], rnd)


def layer(kind: str, w: dict, x, seg, config: dict, lower=None):
    """One layer on one row: ``x`` (T, D) -> (T, D).  ``w`` holds the
    layer's leaves under their short names."""
    import jax

    rnd = _rounder(lower)
    res, eps = config["residual_multiplier"], config["rms_norm_eps"]
    mixer = mamba if kind == "mamba" else attention
    x = x + res * mixer(w, rms(x, w["norm1"], eps), seg, config, rnd)
    h = rms(x, w["norm2"], eps)
    act = jax.nn.silu(_dot("td,df->tf", h, w["mlp_gate"], rnd)) \
        * _dot("td,df->tf", h, w["mlp_up"], rnd)
    return x + res * _dot("tf,fd->td", act, w["mlp_down"], rnd)


def embed(table, tokens, config: dict):
    return config["embedding_multiplier"] * table[tokens]


def logits_of(x, table, final_norm, config: dict, lower=None):
    """(T, D) -> (T, V): the tied head."""
    return _dot("td,vd->tv", rms(x, final_norm, config["rms_norm_eps"]),
                table, _rounder(lower)) / config["logits_scaling"]


def loss_sum(x, table, final_norm, tokens, seg, config: dict, lower=None):
    """Sum (not mean) of one row's cross-entropies over the positions whose
    next token is the same document's."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits_of(x, table, final_norm, config, lower))
    picked = jnp.take_along_axis(logp[:-1], tokens[1:, None], axis=1)[:, 0]
    return -jnp.sum(jnp.where(seg[1:] == seg[:-1], picked, 0.0))


def forward(weights: dict, tokens, seg, config: dict, lower=None):
    """Logits (B, T, V) of a batch of packed rows: the whole model at once,
    for sizes that allow it (the tests)."""
    import jax

    def row(u, s):
        x = embed(weights["embed"], u, config)
        for i, kind in enumerate(layer_types(config)):
            x = layer(kind, _layer_leaves(weights, i), x, s, config, lower)
        return logits_of(x, weights["embed"], weights["final_norm"], config,
                         lower)

    return jax.vmap(row)(tokens, seg)


def _layer_leaves(weights: dict, i: int) -> dict:
    pre = f"l{i:02d}/"
    return {k[len(pre):]: v for k, v in weights.items() if k.startswith(pre)}


def _freeze(config: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in config.items()
                        if isinstance(v, (int, float, str, list))))


@functools.lru_cache(maxsize=16)
def _compiled(frozen_config, lower):
    """The jitted pieces of a step: a layer's forward and its gradient by
    kind, the head's loss with its gradients, the embedding's gradient."""
    import jax

    config = {k: list(v) if isinstance(v, tuple) else v
              for k, v in frozen_config}

    def layer_rows(kind):
        return lambda w, x, seg: jax.vmap(
            lambda xr, sr: layer(kind, w, xr, sr, config, lower))(x, seg)

    def layer_grad(kind):
        def grad(w, x, seg, dy):
            _, vjp = jax.vjp(lambda w_, x_: layer_rows(kind)(w_, x_, seg),
                             w, x)
            return vjp(dy)
        return grad

    def head(x, table, final_norm, tokens, seg, scale):
        total = jax.vmap(lambda xr, ur, sr: loss_sum(
            xr, table, final_norm, ur, sr, config, lower))(x, tokens, seg)
        return total.sum() * scale

    def embed_grad(table, tokens, dx):
        return jax.vjp(lambda e: embed(e, tokens, config), table)[1](dx)[0]

    return {
        "embed": jax.jit(lambda e, u: embed(e, u, config)),
        "layer": {k: jax.jit(layer_rows(k)) for k in ("mamba", "attention")},
        "layer_grad": {k: jax.jit(layer_grad(k))
                       for k in ("mamba", "attention")},
        "head": jax.jit(jax.value_and_grad(head, argnums=(0, 1, 2))),
        "embed_grad": jax.jit(embed_grad),
    }


@functools.lru_cache(maxsize=None)
def _adamw_fn(b1, b2, eps, weight_decay, learning_rate):
    """One AdamW step on one leaf as the optimizer the configuration names
    defines it (bias-corrected moments; decoupled weight decay added before
    the learning rate is applied)."""
    import jax
    import jax.numpy as jnp

    def update(w, g, mu, nu, count):
        mu = b1 * mu + (1.0 - b1) * g
        nu = b2 * nu + (1.0 - b2) * g * g
        m_hat = mu / (1.0 - b1 ** count)
        v_hat = nu / (1.0 - b2 ** count)
        step = m_hat / (jnp.sqrt(v_hat) + eps) + weight_decay * w
        return (w - learning_rate * step, mu, nu,
                jnp.sqrt(jnp.sum(jnp.square(g))))

    return jax.jit(update, donate_argnums=(0, 2, 3))


def train_step(weights: dict, state: dict, batch: dict, config: dict,
               lower=None):
    """One training step in place on ``weights`` and ``state`` (``mu``,
    ``nu``, ``count``), a layer at a time.  Returns the mean loss and the
    per-leaf norm of the gradient the optimizer got."""
    import jax.numpy as jnp

    fns = _compiled(_freeze(config), lower)
    opt = config["optimizer"]
    adamw = _adamw_fn(opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"],
                      opt["learning_rate"])
    tokens, seg = jnp.asarray(batch["tokens"]), jnp.asarray(batch["segment_ids"])
    kinds = layer_types(config)
    state["count"] += 1
    grad_norms = {}

    def apply(name, grad):
        weights[name], state["mu"][name], state["nu"][name], norm = adamw(
            weights[name], grad, state["mu"][name], state["nu"][name],
            jnp.float32(state["count"]))
        grad_norms[name] = norm

    inputs = [fns["embed"](weights["embed"], tokens)]
    for i, kind in enumerate(kinds):
        inputs.append(fns["layer"][kind](_layer_leaves(weights, i),
                                         inputs[-1], seg))
    counted = int((batch["segment_ids"][:, 1:]
                   == batch["segment_ids"][:, :-1]).sum())
    loss, (dx, d_table, d_norm) = fns["head"](
        inputs.pop(), weights["embed"], weights["final_norm"], tokens, seg,
        jnp.float32(1.0 / max(counted, 1)))
    apply("final_norm", d_norm)
    for i in reversed(range(len(kinds))):
        grads, dx = fns["layer_grad"][kinds[i]](
            _layer_leaves(weights, i), inputs.pop(), seg, dx)
        for short, grad in grads.items():
            apply(f"l{i:02d}/{short}", grad)
        del grads
    apply("embed", d_table + fns["embed_grad"](weights["embed"], tokens, dx))
    return float(loss), {k: float(v) for k, v in grad_norms.items()}


def follow(config: dict, seed: int, batches: list, lower=None) -> dict:
    """Follow the first ``len(batches)`` training steps from the seeded
    weights.  Returns each step's loss, the per-leaf norm of the first
    gradient, and the per-leaf norm of the parameters' change over all the
    steps — the numbers the program's are compared with."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        weights = make_weights(config, seed)
        zeros = jax.jit(jnp.zeros_like)
        state = {"mu": {k: zeros(v) for k, v in weights.items()},
                 "nu": {k: zeros(v) for k, v in weights.items()}, "count": 0}
        losses, grad_norms = [], None
        for batch in batches:
            loss, norms = train_step(weights, state, batch, config, lower)
            losses.append(loss)
            grad_norms = grad_norms or norms
        diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
        change = {name: float(diff(weights[name],
                                   make_leaf(config, seed, name)))
                  for name in list(weights)}
        return {"losses": losses, "grad_norms": grad_norms,
                "change_norms": change}
