"""Plain reference of the ``kimi_linear_48b_a3b`` configuration: weights from
the seed, forward pass, loss, gradients, AdamW and the routing biases, in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.

It imports nothing of the program and takes nothing the program has made.
The model is Kimi-Linear-48B-A3B (``config.json`` of
``moonshotai/Kimi-Linear-48B-A3B-Instruct``, ``model_type`` ``kimi_linear``;
the technical report arXiv:2510.26692 and the public ``modeling_kimi.py``) on
packed rows: tokens ``u`` with segment ids ``s``; layers numbered from 1.

    x = E[u];  per layer  x += mixer(rms(x));  x += FFN(rms(x))
    KDA (``kda_layers``), 32 heads of 128, a the normed input:
           q, k, v = silu(conv4(a W_q)), silu(conv4(a W_k)), silu(conv4(a W_v))
           conv4: c_t = w_3 x_t + w_2 x_{t-1} + w_1 x_{t-2} + w_0 x_{t-3}, a
           term kept where its token is t's document's; a channel at a time
           a head at a time: q = q / sqrt(|q|^2 + 1e-6) / sqrt(128),
           k = k / sqrt(|k|^2 + 1e-6)
           g = -exp(A_log_h) softplus(W_fb (W_fa a) + dt_bias)   (<= 0, a
           number a channel of a head's key);  beta = sigmoid(a W_b)
           token by token, a head at a time, S (128 x 128) zero at a
           document's first token:
               S' = Diag(exp g_t) S;  S = S' + beta_t k_t (v_t - S'^T k_t)^T
               o_t = S^T q_t
           out = W_o (rms_128(o) * sigmoid(W_gb (W_ga a)))     (one scale of
           128 shared by the heads)
    attn (``full_attn_layers``), 32 heads:
           q = a W_q (heads of 192);  [c ; k_pe] = a W_kva (512 + 64)
           [k_nope ; v] a head = rms(c) W_kvb (128 + 128)
           o_h = softmax((q_nope k_nope^T + q_pe k_pe^T) / sqrt(192),
                         mask j <= i and s_j == s_i) v;  no rotation
           out = concat(o) W_o
    FFN:   the dense layer W_2 (silu(h W_1) * (h W_3)); an expert layer
           sc = sigmoid(h W_r);  chosen = top-8 of (sc + b_i)
           g_e = 2.446 sc_e / sum over chosen of sc
           y = Shared(h) + sum over e chosen and held of g_e Expert_e(h)
    loss = mean CE(rms(x_t) W_head, u_{t+1} | s_{t+1} == s_t)   (untied head)
    after the step, every expert layer:  b_e += 0.001 sign(mean(c) - c_e),
           c_e the tokens of the step that chose e

Everything is computed as it is written: the recurrence token by token (a
``lax.scan`` over the row, no chunks, no triangular solve), every held expert
over every token, masked by the choice; the convolution as four shifted sums;
attention a block of queries against the whole row; the loss a block of
tokens at a time; a training step a layer at a time, the layer's forward
redone for its gradient and AdamW applied to the layer at once, so that
weights and both moments (12 bytes a parameter) and one layer's float32
working set fit a chip.  **One departure so that 8,192 tokens x 32 heads x
128 x 128 fit**: the scan is checkpointed by blocks of ``SCAN_BLOCK`` tokens
(the state entering a block is kept, the inside is redone in the backward
pass); the numbers are those of the plain scan.

Departures from the published model: depth (the first ``num_hidden_layers``
of the 27 layers), the experts held (``experts_held`` of the router's 256:
what the others would have added is left out), vocabulary (the first
``vocab_size`` rows), random weights from the seed (normal, ``init_std``; the
matrices that write into the residual stream ``init_std / sqrt(2 x 27)``; the
convolutions' taps uniform in +-1/2; ``A_log = log U(1, 16)``, ``dt_bias =
softplus^-1(exp U(log 0.001, log 0.1))``; unit norms), and what the
configuration's file lists under ``assumed``.  Departure from the program:
every activation stays float32 (the program's are bfloat16).

``lower`` names the control's precision (``"float8"`` for this bfloat16
configuration): the operands of every matrix product but the router's — which
the configuration states in float32 — are cast to ``float8_e4m3fn`` and back
before the product, and so are the gradients that flow back through those
casts; the queries, keys and values enter the recurrence rounded the same
way (they are the operands of its products), the decay, the step size and the
state stay float32 as the configuration states them.
"""

from __future__ import annotations

import functools
import math

import numpy as np

QUERY_BLOCK = 256
LOSS_BLOCK = 2048
SCAN_BLOCK = 64
L2_EPS = 1e-6
A_RANGE = (1.0, 16.0)
DT_RANGE = (0.001, 0.1)
#: the groups of the configuration's file the reference reads
NESTED = ("published", "linear_attn_config")


def router_width(config: dict) -> int:
    """The experts the router scores: the published count."""
    return config["published"]["num_experts"]


def layers(config: dict) -> list:
    """``(prefix, mixer, ffn)`` of the layers run, in forward order: the
    first ``num_hidden_layers`` of the published layers (numbered from 1 in
    ``linear_attn_config``), ``"kda"`` or ``"full_attention"``, the first
    ``first_k_dense_replace`` of them with the dense feed-forward."""
    kda = config["linear_attn_config"]["kda_layers"]
    full = config["linear_attn_config"]["full_attn_layers"]
    out = []
    for i in range(config["num_hidden_layers"]):
        if (i + 1 in kda) == (i + 1 in full):
            raise ValueError(f"layer {i + 1} is in one of kda_layers and "
                             "full_attn_layers")
        out.append((f"l{i:02d}/", "kda" if i + 1 in kda else "full_attention",
                    "dense" if i < config["first_k_dense_replace"]
                    else "experts"))
    return out


def kda_sizes(config: dict) -> tuple:
    """``(heads, head size)`` of a KDA layer."""
    linear = config["linear_attn_config"]
    return linear["num_heads"], linear["head_dim"]


def leaf_shapes(config: dict) -> dict:
    """Name -> (shape, kind) of every parameter, forward order.  ``kind``
    names the leaf's initialisation: ``"ones"``, ``"normal"`` (standard
    deviation ``init_std``), ``"normal_out"``, the matrices that write into
    the residual stream (:func:`init_stds`), ``"taps"``, a convolution's
    (uniform in +-1/sqrt(taps)), ``"a_log"`` (``log U(1, 16)``) or
    ``"dt_bias"`` (``softplus^-1`` of a log-uniform step in 0.001-0.1)."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    kh, hd = kda_sizes(config)
    p = kh * hd
    nope, r = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    latent, taps = config["kv_lora_rank"], \
        config["linear_attn_config"]["short_conv_kernel_size"]
    f, held = config["moe_intermediate_size"], len(config["experts_held"])
    shared = f * config["num_shared_experts"]
    out = {"embed": ((config["vocab_size"], d), "normal")}
    for pre, mixer, ffn in layers(config):
        out[pre + "norm1"] = ((d,), "ones")
        if mixer == "kda":
            for name in ("q", "k", "v"):
                out[pre + f"kda_{name}"] = ((d, p), "normal")
            for name in ("q", "k", "v"):
                out[pre + f"kda_{name}_conv"] = ((taps, p), "taps")
            out[pre + "kda_f_a"] = ((d, hd), "normal")
            out[pre + "kda_f_b"] = ((hd, p), "normal")
            out[pre + "kda_dt_bias"] = ((p,), "dt_bias")
            out[pre + "kda_A_log"] = ((kh,), "a_log")
            out[pre + "kda_beta"] = ((d, kh), "normal")
            out[pre + "kda_g_a"] = ((d, hd), "normal")
            out[pre + "kda_g_b"] = ((hd, p), "normal")
            out[pre + "kda_o_norm"] = ((hd,), "ones")
            out[pre + "kda_wo"] = ((p, d), "normal_out")
        else:
            out[pre + "wq"] = ((d, heads * (nope + r)), "normal")
            out[pre + "kv_a"] = ((d, latent + r), "normal")
            out[pre + "kv_a_norm"] = ((latent,), "ones")
            out[pre + "kv_b"] = ((latent, heads * (
                nope + config["v_head_dim"])), "normal")
            out[pre + "wo"] = ((heads * config["v_head_dim"], d),
                               "normal_out")
        out[pre + "norm2"] = ((d,), "ones")
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
def _leaf_fn(shape: tuple, kind: str, std):
    """One compiled maker for every leaf of a shape and kind."""
    import jax
    import jax.numpy as jnp

    def make(key, index):
        key = jax.random.fold_in(key, index)
        if kind == "ones":
            return jnp.ones(shape, jnp.float32)
        if kind == "taps":
            bound = 1.0 / math.sqrt(shape[0])
            return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
        if kind == "a_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              *A_RANGE))
        if kind == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, *(math.log(b) for b in DT_RANGE)))
            return dt + jnp.log(-jnp.expm1(-dt))    # softplus^-1(dt)
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


def swiglu(h, gate, up, down, rnd):
    import jax

    return _dot("tf,fd->td", jax.nn.silu(_dot("td,df->tf", h, gate, rnd))
                * _dot("td,df->tf", h, up, rnd), down, rnd)


def short_conv(v, taps, seg):
    """``c_t = sum_j taps[K-1-j] v_{t-j}`` over the ``j < K`` whose token
    ``t-j`` exists and is in ``t``'s document: K shifted sums.  ``v`` (T, C),
    ``taps`` (K, C), ``seg`` (T,)."""
    import jax.numpy as jnp

    k, t = taps.shape[0], v.shape[0]
    at = jnp.arange(t)
    c = v * taps[k - 1]
    for j in range(1, k):
        back = jnp.roll(v, j, axis=0)
        same = (at >= j) & (jnp.roll(seg, j) == seg)
        c = c + jnp.where(same[:, None], back, 0.0) * taps[k - 1 - j]
    return c


def delta_rule(q, k, v, g, beta, seg):
    """The channel-wise gated delta rule of one row, token by token: ``q``,
    ``k``, ``g`` (T, H, K), ``v`` (T, H, V), ``beta`` (T, H), ``seg`` (T,)
    -> (T, H, V).  The state is zero entering a document."""
    import jax
    import jax.numpy as jnp

    t, heads, dk = q.shape
    first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    size = next(s for s in range(min(SCAN_BLOCK, t), 0, -1) if t % s == 0)

    def token(state, inp):
        q_t, k_t, v_t, g_t, b_t, new = inp
        state = jnp.where(new, 0.0, state) * jnp.exp(g_t)[:, :, None]
        u = b_t[:, None] * (v_t - jnp.sum(state * k_t[:, :, None], axis=1))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    def tokens(state, inp):
        return jax.lax.scan(token, state, inp)

    blocks = tuple(a.reshape((t // size, size) + a.shape[1:])
                   for a in (q, k, v, g, beta, first))
    _, o = jax.lax.scan(jax.checkpoint(tokens),
                        jnp.zeros((heads, dk, v.shape[-1]), jnp.float32),
                        blocks)
    return o.reshape((t,) + o.shape[2:])


def kda_mixer(w, h, seg, config, rnd):
    """Kimi Delta Attention on one row: ``h`` (T, D) -> (T, D)."""
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    heads, hd = kda_sizes(config)

    def heads_of(name):
        x = jax.nn.silu(short_conv(_dot("td,de->te", h, w["kda_" + name],
                                        rnd), w[f"kda_{name}_conv"], seg))
        return x.reshape(t, heads, hd)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                 + L2_EPS)

    q, k, v = unit(heads_of("q")) / math.sqrt(hd), unit(heads_of("k")), \
        heads_of("v")
    g = -jnp.exp(w["kda_A_log"])[:, None] * jax.nn.softplus(
        _dot("tr,re->te", _dot("td,dr->tr", h, w["kda_f_a"], rnd),
             w["kda_f_b"], rnd).reshape(t, heads, hd)
        + w["kda_dt_bias"].reshape(heads, hd))
    beta = jax.nn.sigmoid(_dot("td,dh->th", h, w["kda_beta"], rnd))
    o = delta_rule(rnd(q), rnd(k), rnd(v), g, beta, seg)
    o = rms(o, w["kda_o_norm"], config["rms_norm_eps"]).reshape(t, heads * hd)
    gate = jax.nn.sigmoid(_dot(
        "tr,re->te", _dot("td,dr->tr", h, w["kda_g_a"], rnd), w["kda_g_b"],
        rnd))
    return _dot("te,ed->td", o * gate, w["kda_wo"], rnd)


def attention(w, h, seg, config, rnd):
    """NoPE latent attention on one row: ``h`` (T, D) -> (T, D); a head's
    key is 192 wide (its own 128 and 64 shared by the heads), its value
    128."""
    import jax
    import jax.numpy as jnp

    t, heads = h.shape[0], config["num_attention_heads"]
    nope, r = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    latent, vd = config["kv_lora_rank"], config["v_head_dim"]
    q = _dot("td,de->te", h, w["wq"], rnd).reshape(t, heads, nope + r)
    kv_a = _dot("td,dr->tr", h, w["kv_a"], rnd)
    c_kv = rms(kv_a[:, :latent], w["kv_a_norm"], config["rms_norm_eps"])
    kv = _dot("tr,re->te", c_kv, w["kv_b"], rnd).reshape(t, heads, nope + vd)
    k_nope, v, k_pe = kv[..., :nope], kv[..., nope:], kv_a[:, latent:]
    at = jnp.arange(t)
    size = next(s for s in range(min(QUERY_BLOCK, t), 0, -1) if t % s == 0)

    def block(args):
        qb, sb, ab = args
        s = (_dot("ihd,jhd->hij", qb[..., :nope], k_nope, rnd)
             + _dot("ihr,jr->hij", qb[..., nope:], k_pe, rnd)
             ) / math.sqrt(nope + r)
        mask = (ab[:, None] >= at[None, :]) & (sb[:, None] == seg[None, :])
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return _dot("hij,jhd->ihd", p, v, rnd)

    o = jax.lax.map(jax.checkpoint(block), (
        q.reshape(t // size, size, heads, nope + r), seg.reshape(-1, size),
        at.reshape(-1, size)))
    return _dot("te,ed->td", o.reshape(t, heads * vd), w["wo"], rnd)


def route(w_router, bias, h, config):
    """``(gates, chosen)``, both (T, E): the weight of every expert for
    every token (zero where it was not chosen) and the choice as 0/1.  The
    router's product is float32 whatever the control rounds."""
    import jax
    import jax.numpy as jnp

    k = config["num_experts_per_token"]
    sc = jax.nn.sigmoid(jnp.einsum("td,de->te", h, w_router,
                                   precision=jax.lax.Precision.HIGHEST))
    best = jnp.argsort(-(jax.lax.stop_gradient(sc) + bias), axis=-1,
                       stable=True)[:, :k]
    chosen = jnp.sum(best[:, :, None] == jnp.arange(sc.shape[1]), axis=1)
    picked = sc * chosen
    if config["moe_renormalize"]:
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


def layer(mixer: str, ffn: str, w: dict, x, seg, bias, config: dict,
          lower=None):
    """One layer on one row: ``x`` (T, D) -> ``(x, counts)``.  ``w`` holds
    the layer's leaves under their short names; ``counts`` (E,) is zero for
    a dense layer."""
    import jax.numpy as jnp

    rnd, eps = _rounder(lower), config["rms_norm_eps"]
    h = rms(x, w["norm1"], eps)
    x = x + (kda_mixer(w, h, seg, config, rnd) if mixer == "kda"
             else attention(w, h, seg, config, rnd))
    h = rms(x, w["norm2"], eps)
    if ffn == "dense":
        return x + swiglu(h, w["mlp_gate"], w["mlp_up"], w["mlp_down"],
                          rnd), jnp.zeros((router_width(config),), jnp.int32)
    y, counts = experts(w, h, bias, config, rnd)
    return x + y, counts


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
    import jax.numpy as jnp

    rows = sum(1 for _, _, ffn in layers(config) if ffn == "experts")
    return jnp.zeros((rows, router_width(config)), jnp.float32)


def forward(weights: dict, tokens, seg, config: dict, bias=None, lower=None):
    """``(logits (B, T, V), loss, counts (expert layers, E))`` of a batch of
    packed rows: the whole model at once, for sizes that allow it (the
    tests)."""
    import jax
    import jax.numpy as jnp

    bias = zero_bias(config) if bias is None else bias
    scale = loss_scale(seg)
    tokens, seg = jnp.asarray(tokens), jnp.asarray(seg)

    def row(u, s):
        x, counts = weights["embed"][u], []
        for pre, mixer, ffn in layers(config):
            b = bias[len(counts)] if ffn == "experts" else None
            x, c = layer(mixer, ffn, _layer_leaves(weights, pre), x, s, b,
                         config, lower)
            if ffn == "experts":
                counts.append(c)
        loss = tail(_tail_leaves(weights), x, u, s, scale, config, lower)
        logits = logits_of(x, weights["head"], weights["final_norm"],
                           config, lower)
        return logits, loss, jnp.stack(counts)

    logits, loss, counts = jax.vmap(row)(tokens, seg)
    return logits, loss.sum(), counts.sum(0)


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
                        or k in NESTED))


@functools.lru_cache(maxsize=16)
def _compiled(frozen_config, lower):
    """The jitted pieces of a step: a layer's forward and its gradient by
    kind, the tail's loss with its gradients, the embedding's gradient."""
    import jax

    config = {k: (dict(v) if k in NESTED else list(v))
              if isinstance(v, tuple) else v for k, v in frozen_config}

    def layer_rows(kind):
        def rows(w, x, seg, bias):
            y, counts = jax.vmap(lambda xr, sr: layer(
                *kind, w, xr, sr, bias, config, lower))(x, seg)
            return y, counts.sum(0)
        return rows

    def layer_grad(kind):
        def grad(w, x, seg, bias, dy):
            _, vjp, _ = jax.vjp(
                lambda w_, x_: layer_rows(kind)(w_, x_, seg, bias),
                w, x, has_aux=True)
            return vjp(dy)
        return grad

    def tail_rows(w_tail, x, tokens, seg, scale):
        return jax.vmap(lambda xr, ur, sr: tail(
            w_tail, xr, ur, sr, scale, config, lower))(x, tokens, seg).sum()

    def embed_grad(table, tokens, dx):
        return jax.vjp(lambda e: e[tokens], table)[1](dx)[0]

    kinds = sorted({(mixer, ffn) for _, mixer, ffn in layers(config)})
    return {
        "embed": jax.jit(lambda e, u: e[u]),
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
    import jax.numpy as jnp

    fns = _compiled(_freeze(config), lower)
    opt = config["optimizer"]
    adamw = _adamw_fn(opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"],
                      opt["learning_rate"])
    tokens, seg = jnp.asarray(batch["tokens"]), jnp.asarray(batch["segment_ids"])
    scale = jnp.float32(loss_scale(batch["segment_ids"]))
    bias = state["bias"]
    state["count"] += 1
    grad_norms = {}

    def apply(name, grad):
        weights[name], state["mu"][name], state["nu"][name], norm = adamw(
            weights[name], grad, state["mu"][name], state["nu"][name],
            jnp.float32(state["count"]))
        grad_norms[name] = norm

    inputs, rows, counts = [fns["embed"](weights["embed"], tokens)], [], []
    for pre, mixer, ffn in layers(config):
        rows.append(len(counts) if ffn == "experts" else None)
        b = bias[rows[-1]] if ffn == "experts" else None
        x, c = fns["layer"][mixer, ffn](_layer_leaves(weights, pre),
                                        inputs[-1], seg, b)
        inputs.append(x)
        if ffn == "experts":
            counts.append(c)
    loss, (d_tail, dx) = fns["tail"](_tail_leaves(weights), inputs.pop(),
                                     tokens, seg, scale)
    apply("final_norm", d_tail["final_norm"])
    for (pre, mixer, ffn), row in zip(reversed(layers(config)),
                                      reversed(rows)):
        b = bias[row] if ffn == "experts" else None
        grads, dx = fns["layer_grad"][mixer, ffn](
            _layer_leaves(weights, pre), inputs.pop(), seg, b, dx)
        for short, grad in grads.items():
            apply(pre + short, grad)
        del grads
    apply("head", d_tail["head"])
    apply("embed", fns["embed_grad"](weights["embed"], tokens, dx))
    counts = jnp.stack(counts)
    load = counts.astype(jnp.float32)
    state["bias"] = bias + config["bias_update_speed"] * jnp.sign(
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
