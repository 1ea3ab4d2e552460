"""Plain reference of the ``trinity_mini`` configuration: weights from the
seed, forward pass, loss, gradients, AdamW and the routing biases, in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``.

It imports nothing of the program and takes nothing the program has made.
The model is Trinity-Mini (``config.json`` of ``arcee-ai/Trinity-Mini``,
``model_type`` ``afmoe``) on packed rows: tokens ``u`` with segment ids ``s``,
``p_t`` the index of token t in its document, ``i`` and ``j`` places in the
row, D = 2048.

    x = E[u] * sqrt(D)                                       (mup_enabled)
    per layer  x += rms(attn(rms(x; norm1)); norm2)
               x += rms(ffn(rms(x; norm3)); norm4)
    attn:  q = h W_q (32 heads of 128), k = h W_k, v = h W_v (4 heads of 128),
           g = h W_g (4096)
           every query and key head: rms over its 128 numbers times one scale
           shared by the heads; **in a sliding_attention layer only** then
           turned at p_t, halves rotated, f_i = 10000 ** (-i / 64); in a
           full_attention layer not turned at all
           o_h = softmax(q_h k_{h // 8}^T / sqrt(128), mask j <= i and
                 s_j == s_i and, in a sliding layer, i - j < 2048) v_{h // 8}
           out = (concat(o) * sigmoid(g)) W_o
    ffn, published layer < num_dense_layers:
           W_down (silu(h W_gate) * (h W_up))
    ffn, every later layer:
           sc = sigmoid(h W_r) over all 128;  chosen = top-8 of (sc + b_l)
           g_e = 2.826 sc_e / (sum over chosen of sc + 1e-20)
           y = Shared(h) + sum over e chosen and held of
               g_e W_2e (silu(h W_1e) * (h W_3e))
    loss = mean CE(rms(x_t) W_head, u_{t+1} | s_{t+1} == s_t)   (untied head)
    after the step, every expert layer:  b_e += 0.001 sign(mean(c) - c_e),
           c_e the tokens of the step that chose e

Everything is computed as it is written: every held expert over every token,
masked by the choice; attention one masked softmax of a block of queries
against the whole row, the mask written from the three conditions above (no
block is skipped and no maximum is carried); the loss a block of tokens at a
time; a training step a layer at a time, the layer's forward redone for its
gradient and AdamW applied to the layer at once, so that weights and both
moments (12 bytes a parameter) and one layer's float32 working set fit a
chip.

Departures from the published model: depth (``layers_run`` of the 32 layers,
the leading dense layers counted once), the experts held (``experts_held`` of
the router's 128: what the others would have added is left out, before
``norm4``), vocabulary (the first ``vocab_size`` rows), random weights from
the seed (normal, ``init_std``; the matrices that write into the residual
stream ``init_std / sqrt(2 x 32)``; unit norms but for the post-norms'
scales, ``post_norm_init``), and what the configuration's file lists under
``assumed``.  Departure from the program: every activation stays float32
(the program's are bfloat16).

``lower`` names the control's precision (``"float8"`` for this bfloat16
configuration): the operands of every matrix product but the router's — which
the configuration states in float32 — are cast to ``float8_e4m3fn`` and back
before the product, and so are the gradients that flow back through those
casts.  The softmaxes, the sigmoids and the norms are no matrix products and
stay float32.
"""

from __future__ import annotations

import functools
import math

import numpy as np

QUERY_BLOCK = 256
LOSS_BLOCK = 2048


def router_width(config: dict) -> int:
    """The experts the router scores: the published count."""
    return config["published"]["num_experts"]


def layers(config: dict) -> list:
    """``(prefix, mixer, ffn)`` of the layers run, in forward order: the
    published ``layer_types`` at ``layers_run``; a layer whose published
    index is under the published ``num_dense_layers`` has the dense SwiGLU,
    every later one the experts."""
    dense = config["published"]["num_dense_layers"]
    return [(f"l{i:02d}/", config["layer_types"][at],
             "dense" if at < dense else "experts")
            for i, at in enumerate(config["layers_run"])]


def leaf_shapes(config: dict) -> dict:
    """Name -> (shape, kind) of every parameter, forward order.  ``kind``
    names the leaf's initialisation: ``"ones"``, ``"post_scale"`` (the two
    post-norms' scales: ``post_norm_init`` everywhere), ``"normal"``
    (standard deviation ``init_std``: the embedding too) or
    ``"normal_out"``, the matrices that write into the residual stream
    (:func:`init_stds`)."""
    d, hd = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    f, held = config["moe_intermediate_size"], len(config["experts_held"])
    shared = f * config["num_shared_experts"]
    out = {"embed": ((config["vocab_size"], d), "normal")}
    for pre, mixer, ffn in layers(config):
        if mixer not in ("sliding_attention", "full_attention"):
            raise ValueError(f"unknown layer type {mixer!r}")
        out[pre + "norm1"] = ((d,), "ones")
        out[pre + "wq"] = ((d, heads * hd), "normal")
        out[pre + "wk"] = ((d, kv * hd), "normal")
        out[pre + "wv"] = ((d, kv * hd), "normal")
        out[pre + "wg"] = ((d, heads * hd), "normal")
        out[pre + "q_norm"] = ((hd,), "ones")
        out[pre + "k_norm"] = ((hd,), "ones")
        out[pre + "wo"] = ((heads * hd, d), "normal_out")
        out[pre + "norm2"] = ((d,), "post_scale")
        out[pre + "norm3"] = ((d,), "ones")
        if ffn == "dense":
            width = config["intermediate_size"]
            out[pre + "mlp_gate"] = ((d, width), "normal")
            out[pre + "mlp_up"] = ((d, width), "normal")
            out[pre + "mlp_down"] = ((width, d), "normal_out")
        else:
            out[pre + "router"] = ((d, router_width(config)), "normal")
            out[pre + "shared_gate"] = ((d, shared), "normal")
            out[pre + "shared_up"] = ((d, shared), "normal")
            out[pre + "shared_down"] = ((shared, d), "normal_out")
            out[pre + "experts_gate"] = ((held, d, f), "normal")
            out[pre + "experts_up"] = ((held, d, f), "normal")
            out[pre + "experts_down"] = ((held, f, d), "normal_out")
        out[pre + "norm4"] = ((d,), "post_scale")
    out["final_norm"] = ((d,), "ones")
    out["head"] = ((config["vocab_size"], d), "normal")
    return out


def init_stds(config: dict) -> dict:
    """The standard deviation of each kind of matrix: ``init_std``; for a
    block's output projection ``init_std / sqrt(2 L)``, ``L`` the published
    depth (two blocks a layer write into the residual stream: the scaled
    initialisation of GPT-2 and Megatron-LM); and, no deviation but a
    value, what every number of a post-norm's scale starts at,
    ``post_norm_init`` (the file's ``assumed`` says why)."""
    std = config["init_std"]
    return {"normal": std, "post_scale": config["post_norm_init"],
            "normal_out": std / math.sqrt(
                2 * config["published"]["num_hidden_layers"])}


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


@functools.lru_cache(maxsize=None)
def _leaf_fn(shape: tuple, kind: str, std):
    """One compiled maker for every leaf of a shape and kind."""
    import jax
    import jax.numpy as jnp

    def make(key, index):
        key = jax.random.fold_in(key, index)
        if kind == "ones":
            return jnp.ones(shape, jnp.float32)
        if kind == "post_scale":
            return jnp.full(shape, std, jnp.float32)
        return std * jax.random.normal(key, shape, jnp.float32)

    return jax.jit(make)


def make_leaf(config: dict, seed: int, name: str):
    """One parameter, float32, on the device: every leaf is drawn from its
    own key, so any can be made again without the rest."""
    shapes = leaf_shapes(config)
    shape, kind = shapes[name]
    return _leaf_fn(tuple(shape), kind, init_stds(config).get(kind))(
        seed_key(seed), list(shapes).index(name))


def make_weights(config: dict, seed: int) -> dict:
    return {name: make_leaf(config, seed, name)
            for name in leaf_shapes(config)}


def positions(segment_ids) -> np.ndarray:
    """(B, T) int32: the index of every token inside its document."""
    seg = np.asarray(segment_ids)
    out = np.zeros(seg.shape, np.int32)
    for r, row in enumerate(seg):
        starts = np.flatnonzero(np.r_[True, row[1:] != row[:-1]])
        lengths = np.diff(np.r_[starts, len(row)])
        out[r] = np.arange(len(row)) - np.repeat(starts, lengths)
    return out


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


def rotate(x, pos, theta):
    """RoPE on the last axis of ``x`` (T, ..., R) at positions ``pos``
    (T,): ``x cos + rotate_half(x) sin``, ``rotate_half([a | b]) = [-b |
    a]``, the angle of pair ``i`` being ``pos * theta ** (-2 i / R)``."""
    import jax.numpy as jnp

    r = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = pos.astype(jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (r,))
    half = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], axis=-1)
    return x * jnp.cos(angle) + half * jnp.sin(angle)


def swiglu(h, gate, up, down, rnd):
    import jax

    return _dot("tf,fd->td", jax.nn.silu(_dot("td,df->tf", h, gate, rnd))
                * _dot("td,df->tf", h, up, rnd), down, rnd)


def rotated(mixer: str) -> bool:
    """Whether a layer of type ``mixer`` turns its queries and keys: the
    sliding layers do, the full ones carry no position signal."""
    return mixer == "sliding_attention"


def windowed(mixer: str) -> bool:
    """Whether a layer of type ``mixer`` sees only ``sliding_window`` keys."""
    return mixer == "sliding_attention"


def output_gate(o, g):
    """Attention's output (T, heads hd) times the sigmoid of the gate's
    projection, element by element."""
    import jax

    return o * jax.nn.sigmoid(g)


def post_norm(y, w, eps):
    """What a half of a layer adds to the residual stream, normed."""
    return rms(y, w, eps)


def attention(w, h, seg, pos, config, rnd, mixer):
    """Gated QK-normed grouped-query attention on one row, as a layer of
    type ``mixer``: ``h`` (T, D) -> (T, D).  Query head ``i`` reads key head
    ``i // (heads / kv)``.  One masked softmax over every key of the row, a
    block of queries at a time so that it fits: the mask is ``j <= i``, the
    same document and, in a ``sliding_attention`` layer, ``i - j <
    sliding_window``."""
    import jax
    import jax.numpy as jnp

    t, heads = h.shape[0], config["num_attention_heads"]
    kv, hd, eps = config["num_key_value_heads"], config["head_dim"], \
        config["rms_norm_eps"]
    q = _dot("td,de->te", h, w["wq"], rnd).reshape(t, heads, hd)
    k = _dot("td,de->te", h, w["wk"], rnd).reshape(t, kv, hd)
    v = _dot("td,de->te", h, w["wv"], rnd).reshape(t, kv, hd)
    g = _dot("td,de->te", h, w["wg"], rnd)
    q, k = rms(q, w["q_norm"], eps), rms(k, w["k_norm"], eps)
    if rotated(mixer):
        q = rotate(q, pos, config["rope_theta"])
        k = rotate(k, pos, config["rope_theta"])
    k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
    at = jnp.arange(t)
    size = next(s for s in range(min(QUERY_BLOCK, t), 0, -1) if t % s == 0)

    def block(args):
        qb, sb, ab = args
        s = _dot("ihd,jhd->hij", qb, k, rnd) / math.sqrt(hd)
        mask = (ab[:, None] >= at[None, :]) & (sb[:, None] == seg[None, :])
        if windowed(mixer):
            mask = mask & (ab[:, None] - at[None, :]
                           < config["sliding_window"])
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return _dot("hij,jhd->ihd", p, v, rnd)

    o = jax.lax.map(jax.checkpoint(block), (
        q.reshape(t // size, size, heads, hd), seg.reshape(-1, size),
        at.reshape(-1, size)))
    return _dot("te,ed->td", output_gate(o.reshape(t, heads * hd), g),
                w["wo"], rnd)


def route(w_router, bias, h, config):
    """``(gates, chosen)``, both (T, E): the weight of every expert for
    every token (zero where it was not chosen) and the choice as 0/1.  The
    router's product is float32 whatever the control rounds."""
    import jax
    import jax.numpy as jnp

    k = config["num_experts_per_tok"]
    sc = jax.nn.sigmoid(jnp.einsum("td,de->te", h, w_router,
                                   precision=jax.lax.Precision.HIGHEST))
    best = jnp.argsort(-(jax.lax.stop_gradient(sc) + bias), axis=-1,
                       stable=True)[:, :k]
    chosen = jnp.sum(best[:, :, None] == jnp.arange(sc.shape[1]), axis=1)
    picked = sc * chosen
    if config["route_norm"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                           + config["gate_sum_eps"])
    return config["route_scale"] * picked, chosen


def experts(w, h, bias, config, rnd, held=None):
    """The shared expert and the held routed experts on tokens ``h``
    (N, D): every held expert over every token, masked by the choice; the
    shared expert is not scaled.  ``held`` (default the configuration's
    ``experts_held``) names the experts that ``w``'s stacked weights are.
    Returns ``(y, counts)``."""
    import jax
    import jax.numpy as jnp

    held = config["experts_held"] if held is None else held
    gates, chosen = route(w["router"], bias, h, config)

    def one(y, args):
        gate, up, down, g = args
        return y + g[:, None] * swiglu(h, gate, up, down, rnd), None

    shared = swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"],
                    rnd)
    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(h), (
        w["experts_gate"], w["experts_up"], w["experts_down"],
        gates[:, jnp.asarray(held)].T))
    return shared + y, jnp.sum(chosen, axis=0).astype(jnp.int32)


def layer(mixer: str, ffn: str, w: dict, x, seg, pos, bias, config: dict,
          lower=None):
    """One layer on one row: ``x`` (T, D) -> ``(x, counts)``.  ``w`` holds
    the layer's leaves under their short names; ``counts`` (E,) the tokens
    that chose each of the router's experts, zero for a dense layer."""
    import jax.numpy as jnp

    rnd, eps = _rounder(lower), config["rms_norm_eps"]
    x = x + post_norm(attention(w, rms(x, w["norm1"], eps), seg, pos, config,
                                rnd, mixer), w["norm2"], eps)
    h = rms(x, w["norm3"], eps)
    if ffn == "dense":
        y = swiglu(h, w["mlp_gate"], w["mlp_up"], w["mlp_down"], rnd)
        counts = jnp.zeros((router_width(config),), jnp.int32)
    else:
        y, counts = experts(w, h, bias, config, rnd)
    return x + post_norm(y, w["norm4"], eps), counts


def embed(table, tokens, config: dict):
    """``E[u]``, times ``sqrt(hidden_size)`` where ``mup_enabled``."""
    x = table[tokens]
    return x * math.sqrt(config["hidden_size"]) if config["mup_enabled"] \
        else x


def logits_of(x, head, norm, config: dict, lower=None):
    """(T, D) -> (T, V): the untied head."""
    return _dot("td,vd->tv", rms(x, norm, config["rms_norm_eps"]), head,
                _rounder(lower))


def tail(w_tail: dict, x, tokens, seg, scale, config: dict, lower=None):
    """Everything after the last layer on one row: ``scale`` times the sum
    of the cross-entropies of position ``t`` against ``u_{t+1}`` over the
    positions whose next token is the same document's, a block of tokens at
    a time.  ``w_tail`` holds ``head`` and ``final_norm``."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    valid = (jnp.arange(t) < t - 1) & (jnp.roll(seg, -1) == seg)
    target = jnp.roll(tokens, -1)
    size = next(s for s in range(min(LOSS_BLOCK, t), 0, -1) if t % s == 0)

    def block(args):
        xb, ub, vb = args
        logp = jax.nn.log_softmax(logits_of(
            xb, w_tail["head"], w_tail["final_norm"], config, lower))
        picked = jnp.take_along_axis(logp, ub[:, None], axis=1)[:, 0]
        return -jnp.sum(jnp.where(vb, picked, 0.0))

    return scale * jnp.sum(jax.lax.map(jax.checkpoint(block), (
        x.reshape(t // size, size, -1), target.reshape(-1, size),
        valid.reshape(-1, size))))


def _layer_leaves(weights: dict, pre: str) -> dict:
    return {k[len(pre):]: v for k, v in weights.items() if k.startswith(pre)}


def _tail_leaves(weights: dict) -> dict:
    return {"head": weights["head"], "final_norm": weights["final_norm"]}


def loss_scale(segment_ids) -> float:
    """One over the positions the loss counts, over the whole batch."""
    seg = np.asarray(segment_ids)
    return 1.0 / max(int((seg[:, 1:] == seg[:, :-1]).sum()), 1)


def zero_bias(config: dict):
    """A row of zeros an expert layer."""
    import jax.numpy as jnp

    rows = sum(1 for *_, ffn in layers(config) if ffn == "experts")
    return jnp.zeros((rows, router_width(config)), jnp.float32)


def bias_rows(config: dict) -> list:
    """The routing bias's row of every layer run (None: a dense layer)."""
    rows, n = [], 0
    for *_, ffn in layers(config):
        rows.append(n if ffn == "experts" else None)
        n += ffn == "experts"
    return rows


def forward(weights: dict, tokens, seg, config: dict, bias=None, lower=None):
    """``(logits (B, T, V), loss, counts (expert layers, E))`` of a batch of
    packed rows: the whole model at once, for sizes that allow it (the
    tests)."""
    import jax
    import jax.numpy as jnp

    bias = zero_bias(config) if bias is None else bias
    pos, scale = jnp.asarray(positions(seg)), loss_scale(seg)
    tokens, seg = jnp.asarray(tokens), jnp.asarray(seg)

    def row(u, s, p):
        x, counts = embed(weights["embed"], u, config), []
        for (pre, mixer, ffn), at in zip(layers(config), bias_rows(config)):
            x, c = layer(mixer, ffn, _layer_leaves(weights, pre), x, s, p,
                         None if at is None else bias[at], config, lower)
            if at is not None:
                counts.append(c)
        loss = tail(_tail_leaves(weights), x, u, s, scale, config, lower)
        logits = logits_of(x, weights["head"], weights["final_norm"],
                           config, lower)
        return logits, loss, jnp.stack(counts)

    logits, loss, counts = jax.vmap(row)(tokens, seg, pos)
    return logits, loss.sum(), counts.sum(0)


@functools.lru_cache(maxsize=16)
def _compiled(config_json: str, lower):
    """The jitted pieces of a step: a layer's forward and its gradient by
    kind, the tail's loss with its gradients, the embedding's lookup and its
    gradient.  ``config_json``: the configuration as JSON (a key that
    hashes)."""
    import json

    import jax

    config = json.loads(config_json)

    def layer_rows(kind):
        def rows(w, x, seg, pos, bias):
            y, counts = jax.vmap(lambda xr, sr, pr: layer(
                *kind, w, xr, sr, pr, bias, config, lower))(x, seg, pos)
            return y, counts.sum(0)
        return rows

    def layer_grad(kind):
        def grad(w, x, seg, pos, bias, dy):
            _, vjp, _ = jax.vjp(
                lambda w_, x_: layer_rows(kind)(w_, x_, seg, pos, bias),
                w, x, has_aux=True)
            return vjp(dy)
        return grad

    def tail_rows(w_tail, x, tokens, seg, scale):
        return jax.vmap(lambda xr, ur, sr: tail(
            w_tail, xr, ur, sr, scale, config, lower))(x, tokens, seg).sum()

    def embed_grad(table, tokens, dx):
        return jax.vjp(lambda e: embed(e, tokens, config), table)[1](dx)[0]

    kinds = sorted({(mixer, ffn) for _, mixer, ffn in layers(config)})
    return {
        "embed": jax.jit(lambda e, u: embed(e, u, config)),
        "layer": {k: jax.jit(layer_rows(k)) for k in kinds},
        "layer_grad": {k: jax.jit(layer_grad(k)) for k in kinds},
        "tail": jax.jit(jax.value_and_grad(tail_rows, argnums=(0, 1))),
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
    ``nu``, ``count``, ``bias``), a layer at a time.  Returns the loss, the
    per-leaf norm of the gradient the optimizer got, and the tokens by
    expert, (expert layers, E)."""
    import json

    import jax.numpy as jnp

    fns = _compiled(json.dumps(config, sort_keys=True), lower)
    opt = config["optimizer"]
    adamw = _adamw_fn(opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"],
                      opt["learning_rate"])
    tokens = jnp.asarray(batch["tokens"])
    seg = jnp.asarray(batch["segment_ids"])
    pos = jnp.asarray(positions(batch["segment_ids"]))
    scale = jnp.float32(loss_scale(batch["segment_ids"]))
    bias, rows = state["bias"], bias_rows(config)
    state["count"] += 1
    grad_norms = {}

    def apply(name, grad):
        weights[name], state["mu"][name], state["nu"][name], norm = adamw(
            weights[name], grad, state["mu"][name], state["nu"][name],
            jnp.float32(state["count"]))
        grad_norms[name] = norm

    def bias_of(at):
        return None if at is None else bias[at]

    inputs, counts = [fns["embed"](weights["embed"], tokens)], []
    for (pre, mixer, ffn), at in zip(layers(config), rows):
        x, c = fns["layer"][mixer, ffn](_layer_leaves(weights, pre),
                                        inputs[-1], seg, pos, bias_of(at))
        inputs.append(x)
        if at is not None:
            counts.append(c)
    loss, (d_tail, dx) = fns["tail"](_tail_leaves(weights), inputs.pop(),
                                     tokens, seg, scale)
    for name, grad in d_tail.items():
        apply(name, grad)
    for (pre, mixer, ffn), at in zip(reversed(layers(config)),
                                     reversed(rows)):
        grads, dx = fns["layer_grad"][mixer, ffn](
            _layer_leaves(weights, pre), inputs.pop(), seg, pos, bias_of(at),
            dx)
        for short, grad in grads.items():
            apply(pre + short, grad)
        del grads
    apply("embed", fns["embed_grad"](weights["embed"], tokens, dx))
    counts = jnp.stack(counts)
    load = counts.astype(jnp.float32)
    state["bias"] = bias + config["load_balance_coeff"] * jnp.sign(
        jnp.mean(load, axis=-1, keepdims=True) - load)
    return (float(loss), {k: float(v) for k, v in grad_norms.items()},
            np.asarray(counts))


def follow(config: dict, seed: int, batches: list, lower=None) -> dict:
    """Follow the first ``len(batches)`` training steps from the seeded
    weights and zero biases.  Returns each step's loss, the per-leaf norm
    of the first gradient, and the per-leaf norm of the parameters' change
    over all the steps — the numbers the program's are compared with — and
    beside them the biases at the end and each step's tokens by expert."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        weights = make_weights(config, seed)
        zeros = jax.jit(jnp.zeros_like)
        state = {"mu": {k: zeros(v) for k, v in weights.items()},
                 "nu": {k: zeros(v) for k, v in weights.items()}, "count": 0,
                 "bias": zero_bias(config)}
        losses, counts, grad_norms = [], [], None
        for batch in batches:
            loss, norms, c = train_step(weights, state, batch, config, lower)
            losses.append(loss)
            counts.append(c.tolist())
            grad_norms = grad_norms or norms
        diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
        change = {name: float(diff(weights[name],
                                   make_leaf(config, seed, name)))
                  for name in list(weights)}
        return {"losses": losses, "grad_norms": grad_norms,
                "change_norms": change,
                "bias": np.asarray(state["bias"]).tolist(), "counts": counts}
