"""Plain reference of the ``glm_4_7_flash`` configuration: weights from the
seed, forward pass, both losses, gradients, AdamW and the routing biases, in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.

It imports nothing of the program and takes nothing the program has made.
The model is GLM-4.7-Flash (``config.json`` of ``zai-org/GLM-4.7-Flash``,
``model_type`` ``glm4_moe_lite``): the DeepSeek-V2/V3 layout — latent
attention (arXiv:2405.04434), sigmoid routing with a correction bias and the
multi-token-prediction module (arXiv:2412.19437) — on packed rows: tokens
``u`` with segment ids ``s``, ``p_t`` the index of token t in its document.

    x = E[u];  per layer  x += MLA(rms(x));  x += FFN(rms(x))
    MLA:   c_q = rms(h W_qa);  [q_nope | q_rope] a head = c_q W_qb
           [c_kv | k_rope] = h W_kva;  c_kv = rms(c_kv)
           [k_nope | v] a head = c_kv W_kvb
           q_rope, k_rope turned by RoPE(theta, p_t), halves rotated; k_rope
           is shared by the heads
           o = softmax((q_nope k_nope^T + q_rope k_rope^T) / sqrt(d_qk),
                       mask j <= i and s_j == s_i) v;  out = concat(o) W_o
    FFN 0: W_down (silu(h W_gate) * (h W_up))
    FFN i: sc = sigmoid(h W_r);  chosen = top-k of (sc + b_i)
           g_e = scaling sc_e / sum over chosen of sc
           y = Shared(h) + sum over e chosen and held of g_e Expert_e(h)
    loss = mean CE(rms(x_t) W_head, u_{t+1} | s_{t+1} == s_t)
         + 0.3 mean CE(rms(h'_t) W_head, u_{t+2} | s_t == s_{t+1} == s_{t+2})
    h' = one more expert layer on W_eh [rms_e(E[u_{t+1}]) ; rms_h(rms(x_t))]
    after the step, every expert layer:  b_e += 0.001 sign(mean(c) - c_e),
           c_e the tokens of the step that chose e

Everything is computed as it is written: every held expert over every token,
masked by the choice; attention a block of queries against the whole row;
the losses a block of tokens at a time; a training step a layer at a time,
the layer's forward redone for its gradient and AdamW applied to the layer at
once, so that weights and both moments (12 bytes a parameter) and one layer's
float32 working set fit a chip.

Departures from the published model: depth (layers 0-4 of 47 and the
prediction module), the experts held (``experts_held`` of the router's 64:
what the others would have added is left out), vocabulary (the first
``vocab_size`` rows), random weights from the seed (normal, ``init_std``;
the matrices that write into the residual stream ``init_std / sqrt(2 x 47)``;
unit norms), and what the
configuration's file lists under ``assumed``.  Departure from the program:
every activation stays float32 (the program's are bfloat16).

``lower`` names the control's precision (``"float8"`` for this bfloat16
configuration): the operands of every matrix product but the router's — which
the configuration states in float32 — are cast to ``float8_e4m3fn`` and back
before the product, and so are the gradients that flow back through those
casts.
"""

from __future__ import annotations

import functools
import math

import numpy as np

QUERY_BLOCK = 256
LOSS_BLOCK = 2048


def router_width(config: dict) -> int:
    """The experts the router scores: the published count."""
    return config["published"]["n_routed_experts"]


def layers(config: dict) -> list:
    """``(prefix, kind)`` in forward order, the prediction module last."""
    out = [(f"l{i:02d}/", "dense" if i < config["first_k_dense_replace"]
            else "experts") for i in range(config["num_hidden_layers"])]
    return out + [("mtp/", "experts")] * config["num_nextn_predict_layers"]


def leaf_shapes(config: dict) -> dict:
    """Name -> (shape, kind) of every parameter, forward order.  ``kind``
    names the leaf's initialisation: ``"ones"``, ``"normal"`` (standard
    deviation ``init_std``) or ``"normal_out"``, the matrices that write
    into the residual stream (:func:`init_stds`)."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    f, held = config["moe_intermediate_size"], len(config["experts_held"])
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    shared = f * config["n_shared_experts"]
    out = {"embed": ((config["vocab_size"], d), "normal")}
    for pre, kind in layers(config):
        if pre == "mtp/":
            out[pre + "enorm"] = ((d,), "ones")
            out[pre + "hnorm"] = ((d,), "ones")
            out[pre + "eh_proj"] = ((2 * d, d), "normal")
        out[pre + "norm1"] = ((d,), "ones")
        out[pre + "q_a"] = ((d, config["q_lora_rank"]), "normal")
        out[pre + "q_a_norm"] = ((config["q_lora_rank"],), "ones")
        out[pre + "q_b"] = ((config["q_lora_rank"], heads * qk), "normal")
        out[pre + "kv_a"] = ((d, config["kv_lora_rank"]
                              + config["qk_rope_head_dim"]), "normal")
        out[pre + "kv_a_norm"] = ((config["kv_lora_rank"],), "ones")
        out[pre + "kv_b"] = ((config["kv_lora_rank"], heads * (
            config["qk_nope_head_dim"] + config["v_head_dim"])), "normal")
        out[pre + "wo"] = ((heads * config["v_head_dim"], d), "normal_out")
        out[pre + "norm2"] = ((d,), "ones")
        if kind == "dense":
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
        if pre == "mtp/":
            out[pre + "head_norm"] = ((d,), "ones")
    out["final_norm"] = ((d,), "ones")
    out["head"] = ((config["vocab_size"], d), "normal")
    return out


def init_stds(config: dict) -> dict:
    """The standard deviation of each kind of matrix: ``init_std``, and for
    a block's output projection ``init_std / sqrt(2 L)``, ``L`` the
    published depth (two blocks a layer write into the residual stream:
    the scaled initialisation of GPT-2 and Megatron-LM)."""
    std = config["init_std"]
    return {"normal": std, "normal_out": std / math.sqrt(
        2 * config["published"]["num_hidden_layers"])}


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


@functools.lru_cache(maxsize=None)
def _leaf_fn(shape: tuple, std):
    """One compiled maker for every leaf of a shape and standard deviation
    (None: ones)."""
    import jax
    import jax.numpy as jnp

    def make(key, index):
        if std is None:
            return jnp.ones(shape, jnp.float32)
        return std * jax.random.normal(jax.random.fold_in(key, index),
                                       shape, jnp.float32)

    return jax.jit(make)


def make_leaf(config: dict, seed: int, name: str):
    """One parameter, float32, on the device: every leaf is drawn from its
    own key, so any can be made again without the rest."""
    shapes = leaf_shapes(config)
    shape, kind = shapes[name]
    return _leaf_fn(tuple(shape), init_stds(config).get(kind))(
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


def mla(w, h, seg, pos, config, rnd):
    """Latent attention on one row: ``h`` (T, D) -> (T, D)."""
    import jax
    import jax.numpy as jnp

    t, heads = h.shape[0], config["num_attention_heads"]
    nope, r = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    latent, eps = config["kv_lora_rank"], config["rms_norm_eps"]
    c_q = rms(_dot("td,dr->tr", h, w["q_a"], rnd), w["q_a_norm"], eps)
    q = _dot("tr,re->te", c_q, w["q_b"], rnd).reshape(t, heads, nope + r)
    kv_a = _dot("td,dr->tr", h, w["kv_a"], rnd)
    c_kv = rms(kv_a[:, :latent], w["kv_a_norm"], eps)
    kv = _dot("tr,re->te", c_kv, w["kv_b"], rnd).reshape(
        t, heads, nope + config["v_head_dim"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_nope = q[..., :nope]
    q_rope = rotate(q[..., nope:], pos, config["rope_theta"])
    k_rope = rotate(kv_a[:, latent:], pos, config["rope_theta"])
    at = jnp.arange(t)
    size = next(s for s in range(min(QUERY_BLOCK, t), 0, -1) if t % s == 0)

    def block(args):
        qn, qr, sb, ab = args
        s = (_dot("ihd,jhd->hij", qn, k_nope, rnd)
             + _dot("ihr,jr->hij", qr, k_rope, rnd)) / math.sqrt(nope + r)
        mask = (ab[:, None] >= at[None, :]) & (sb[:, None] == seg[None, :])
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return _dot("hij,jhd->ihd", p, v, rnd)

    o = jax.lax.map(jax.checkpoint(block), (
        q_nope.reshape(t // size, size, heads, nope),
        q_rope.reshape(t // size, size, heads, r), seg.reshape(-1, size),
        at.reshape(-1, size)))
    return _dot("te,ed->td", o.reshape(t, heads * config["v_head_dim"]),
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
    if config["norm_topk_prob"]:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return config["routed_scaling_factor"] * picked, chosen


def experts(w, h, bias, config, rnd, held=None):
    """The shared expert and the held routed experts on tokens ``h``
    (N, D): every held expert over every token, masked by the choice.
    ``held`` (default the configuration's ``experts_held``) names the
    experts that ``w``'s stacked weights are.  Returns ``(y, counts)``."""
    import jax
    import jax.numpy as jnp

    held = config["experts_held"] if held is None else held
    gates, chosen = route(w["router"], bias, h, config)

    def one(y, args):
        gate, up, down, g = args
        return y + g[:, None] * swiglu(h, gate, up, down, rnd), None

    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(h), (
        w["experts_gate"], w["experts_up"], w["experts_down"],
        gates[:, jnp.asarray(held)].T))
    shared = swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"],
                    rnd)
    return shared + y, jnp.sum(chosen, axis=0).astype(jnp.int32)


def layer(kind: str, w: dict, x, seg, pos, bias, config: dict, lower=None):
    """One layer on one row: ``x`` (T, D) -> ``(x, counts)``.  ``w`` holds
    the layer's leaves under their short names; ``counts`` (E,) is zero for
    a dense layer."""
    import jax.numpy as jnp

    rnd, eps = _rounder(lower), config["rms_norm_eps"]
    x = x + mla(w, rms(x, w["norm1"], eps), seg, pos, config, rnd)
    h = rms(x, w["norm2"], eps)
    if kind == "dense":
        return x + swiglu(h, w["mlp_gate"], w["mlp_up"], w["mlp_down"],
                          rnd), jnp.zeros((router_width(config),), jnp.int32)
    y, counts = experts(w, h, bias, config, rnd)
    return x + y, counts


def logits_of(x, head, norm, config: dict, lower=None):
    """(T, D) -> (T, V): the untied head."""
    return _dot("td,vd->tv", rms(x, norm, config["rms_norm_eps"]), head,
                _rounder(lower))


def loss_sum(x, head, norm, tokens, seg, ahead: int, config: dict,
             lower=None):
    """Sum (not mean) of one row's cross-entropies of position ``t``
    against ``u_{t+ahead}``, over the positions whose ``ahead`` next tokens
    are all the same document's; a block of tokens at a time."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    valid = jnp.arange(t) < t - ahead
    for k in range(1, ahead + 1):
        valid = valid & (jnp.roll(seg, -k) == seg)
    target = jnp.roll(tokens, -ahead)
    size = next(s for s in range(min(LOSS_BLOCK, t), 0, -1) if t % s == 0)

    def block(args):
        xb, ub, vb = args
        logp = jax.nn.log_softmax(logits_of(xb, head, norm, config, lower))
        picked = jnp.take_along_axis(logp, ub[:, None], axis=1)[:, 0]
        return -jnp.sum(jnp.where(vb, picked, 0.0))

    return jnp.sum(jax.lax.map(jax.checkpoint(block), (
        x.reshape(t // size, size, -1), target.reshape(-1, size),
        valid.reshape(-1, size))))


def prediction_input(w_mtp, embed, final_norm, x, tokens, config, lower=None):
    """The prediction module's input on one row: (T, D)."""
    import jax.numpy as jnp

    eps = config["rms_norm_eps"]
    both = jnp.concatenate(
        [rms(embed[jnp.roll(tokens, -1)], w_mtp["enorm"], eps),
         rms(rms(x, final_norm, eps), w_mtp["hnorm"], eps)], axis=-1)
    return _dot("te,ed->td", both, w_mtp["eh_proj"], _rounder(lower))


def tail(w_tail: dict, x, tokens, seg, pos, bias, scales, config: dict,
         lower=None):
    """Everything after the last main layer on one row: ``(main loss sum *
    scales[0] + weight * second loss sum * scales[1], counts, the two
    sums)``.  ``w_tail`` holds ``embed``, ``head``, ``final_norm`` and the
    prediction module's leaves under ``mtp``."""
    import jax.numpy as jnp

    main = loss_sum(x, w_tail["head"], w_tail["final_norm"], tokens, seg, 1,
                    config, lower)
    if not config["num_nextn_predict_layers"]:
        zero = jnp.float32(0.0)
        return main * scales[0], (jnp.zeros((0,), jnp.int32), main, zero)
    mtp = w_tail["mtp"]
    h = prediction_input(mtp, w_tail["embed"], w_tail["final_norm"], x,
                         tokens, config, lower)
    h, counts = layer("experts", mtp, h, seg, pos, bias, config, lower)
    second = loss_sum(h, w_tail["head"], mtp["head_norm"], tokens, seg, 2,
                      config, lower)
    return (main * scales[0]
            + config["mtp_loss_weight"] * second * scales[1],
            (counts, main, second))


def _layer_leaves(weights: dict, pre: str) -> dict:
    return {k[len(pre):]: v for k, v in weights.items() if k.startswith(pre)}


def _tail_leaves(weights: dict) -> dict:
    return {"embed": weights["embed"], "head": weights["head"],
            "final_norm": weights["final_norm"],
            "mtp": _layer_leaves(weights, "mtp/")}


def loss_scales(segment_ids) -> tuple:
    """One over the positions each loss counts, over the whole batch."""
    seg = np.asarray(segment_ids)
    same = seg[:, 1:] == seg[:, :-1]
    return (1.0 / max(int(same.sum()), 1),
            1.0 / max(int((same[:, 1:] & same[:, :-1]).sum()), 1))


def zero_bias(config: dict):
    import jax.numpy as jnp

    rows = sum(1 for _, kind in layers(config) if kind == "experts")
    return jnp.zeros((rows, router_width(config)), jnp.float32)


def forward(weights: dict, tokens, seg, config: dict, bias=None, lower=None):
    """``(logits (B, T, V), loss, main loss, second loss, counts (expert
    layers, E))`` of a batch of packed rows: the whole model at once, for
    sizes that allow it (the tests)."""
    import jax
    import jax.numpy as jnp

    bias = zero_bias(config) if bias is None else bias
    pos, scales = jnp.asarray(positions(seg)), loss_scales(seg)
    tokens, seg = jnp.asarray(tokens), jnp.asarray(seg)

    def row(u, s, p):
        x, counts = weights["embed"][u], []
        for pre, kind in layers(config):
            if pre == "mtp/":
                continue
            b = bias[len(counts)] if kind == "experts" else None
            x, c = layer(kind, _layer_leaves(weights, pre), x, s, p, b,
                         config, lower)
            if kind == "experts":
                counts.append(c)
        loss, (c, main, second) = tail(
            _tail_leaves(weights), x, u, s, p, bias[-1], scales, config,
            lower)
        logits = logits_of(x, weights["head"], weights["final_norm"], config,
                           lower)
        return logits, loss, main, second, jnp.stack(counts + [c])

    logits, loss, main, second, counts = jax.vmap(row)(tokens, seg, pos)
    return (logits, loss.sum(), main.sum() * scales[0],
            second.sum() * scales[1], counts.sum(0))


def _freeze(config: dict):
    def frozen(v):
        if isinstance(v, list):
            return tuple(v)
        if isinstance(v, dict):
            return tuple(sorted((k, frozen(x)) for k, x in v.items()
                                if isinstance(x, (int, float, str, list))))
        return v

    return tuple(sorted((k, frozen(v)) for k, v in config.items()
                        if isinstance(v, (int, float, str, list))
                        or k == "published"))


@functools.lru_cache(maxsize=16)
def _compiled(frozen_config, lower):
    """The jitted pieces of a step: a layer's forward and its gradient by
    kind, the tail's loss with its gradients, the embedding's gradient."""
    import jax

    config = {k: (dict(v) if k == "published" else list(v))
              if isinstance(v, tuple) else v for k, v in frozen_config}

    def layer_rows(kind):
        def rows(w, x, seg, pos, bias):
            y, counts = jax.vmap(lambda xr, sr, pr: layer(
                kind, w, xr, sr, pr, bias, config, lower))(x, seg, pos)
            return y, counts.sum(0)
        return rows

    def layer_grad(kind):
        def grad(w, x, seg, pos, bias, dy):
            _, vjp, _ = jax.vjp(
                lambda w_, x_: layer_rows(kind)(w_, x_, seg, pos, bias),
                w, x, has_aux=True)
            return vjp(dy)
        return grad

    def tail_rows(w_tail, x, tokens, seg, pos, bias, scales):
        loss, (counts, main, second) = jax.vmap(
            lambda xr, ur, sr, pr: tail(w_tail, xr, ur, sr, pr, bias, scales,
                                        config, lower))(x, tokens, seg, pos)
        return loss.sum(), (counts.sum(0), main.sum() * scales[0],
                            second.sum() * scales[1])

    def embed_grad(table, tokens, dx):
        return jax.vjp(lambda e: e[tokens], table)[1](dx)[0]

    kinds = ("dense", "experts")
    return {
        "embed": jax.jit(lambda e, u: e[u]),
        "layer": {k: jax.jit(layer_rows(k)) for k in kinds},
        "layer_grad": {k: jax.jit(layer_grad(k)) for k in kinds},
        "tail": jax.jit(jax.value_and_grad(tail_rows, argnums=(0, 1),
                                           has_aux=True)),
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
    per-leaf norm of the gradient the optimizer got, and ``(main loss,
    second loss, counts (expert layers, E))``."""
    import jax.numpy as jnp

    fns = _compiled(_freeze(config), lower)
    opt = config["optimizer"]
    adamw = _adamw_fn(opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"],
                      opt["learning_rate"])
    tokens, seg = jnp.asarray(batch["tokens"]), jnp.asarray(batch["segment_ids"])
    pos = jnp.asarray(positions(batch["segment_ids"]))
    scales = tuple(jnp.float32(s) for s in loss_scales(batch["segment_ids"]))
    main_layers = [(pre, kind) for pre, kind in layers(config)
                   if pre != "mtp/"]
    bias = state["bias"]
    state["count"] += 1
    grad_norms = {}

    def apply(name, grad):
        weights[name], state["mu"][name], state["nu"][name], norm = adamw(
            weights[name], grad, state["mu"][name], state["nu"][name],
            jnp.float32(state["count"]))
        grad_norms[name] = norm

    inputs, rows, counts = [fns["embed"](weights["embed"], tokens)], [], []
    for pre, kind in main_layers:
        rows.append(len(counts) if kind == "experts" else None)
        b = bias[rows[-1]] if kind == "experts" else None
        x, c = fns["layer"][kind](_layer_leaves(weights, pre), inputs[-1],
                                  seg, pos, b)
        inputs.append(x)
        if kind == "experts":
            counts.append(c)
    (loss, (c, main, second)), (d_tail, dx) = fns["tail"](
        _tail_leaves(weights), inputs.pop(), tokens, seg, pos, bias[-1],
        scales)
    if config["num_nextn_predict_layers"]:
        counts.append(c)
    for short, grad in d_tail.pop("mtp").items():
        apply("mtp/" + short, grad)
    apply("final_norm", d_tail["final_norm"])
    apply("head", d_tail["head"])
    for (pre, kind), row in zip(reversed(main_layers), reversed(rows)):
        b = bias[row] if kind == "experts" else None
        grads, dx = fns["layer_grad"][kind](
            _layer_leaves(weights, pre), inputs.pop(), seg, pos, b, dx)
        for short, grad in grads.items():
            apply(pre + short, grad)
        del grads
    apply("embed", d_tail["embed"]
          + fns["embed_grad"](weights["embed"], tokens, dx))
    counts = jnp.stack(counts)
    load = counts.astype(jnp.float32)
    state["bias"] = bias + config["bias_update_speed"] * jnp.sign(
        jnp.mean(load, axis=-1, keepdims=True) - load)
    return (float(loss), {k: float(v) for k, v in grad_norms.items()},
            (float(main), float(second), np.asarray(counts)))


def follow(config: dict, seed: int, batches: list, lower=None) -> dict:
    """Follow the first ``len(batches)`` training steps from the seeded
    weights and zero biases.  Returns each step's loss, the per-leaf norm
    of the first gradient, and the per-leaf norm of the parameters' change
    over all the steps — the numbers the program's are compared with — and
    beside them the two losses apart, the biases at the end and each step's
    tokens by expert."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        weights = make_weights(config, seed)
        zeros = jax.jit(jnp.zeros_like)
        state = {"mu": {k: zeros(v) for k, v in weights.items()},
                 "nu": {k: zeros(v) for k, v in weights.items()}, "count": 0,
                 "bias": zero_bias(config)}
        losses, parts, counts, grad_norms = [], [], [], None
        for batch in batches:
            loss, norms, (main, second, c) = train_step(
                weights, state, batch, config, lower)
            losses.append(loss)
            parts.append([main, second])
            counts.append(c.tolist())
            grad_norms = grad_norms or norms
        diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
        change = {name: float(diff(weights[name],
                                   make_leaf(config, seed, name)))
                  for name in list(weights)}
        return {"losses": losses, "grad_norms": grad_norms,
                "change_norms": change, "loss_parts": parts,
                "bias": np.asarray(state["bias"]).tolist(), "counts": counts}
