"""Hybrid linear-attention / latent-attention mixture-of-experts decoder (the
``kimi_linear`` layout: Kimi Delta Attention mixers, arXiv:2510.26692 — a
channel-wise gated delta rule, arXiv:2412.06464 — three to one beside NoPE
latent attention, arXiv:2405.04434; a leading dense layer, then sigmoid-
routed experts with a correction bias, arXiv:2412.19437, beside a shared
expert), trained on packed rows.

Published shape: ``moonshotai/Kimi-Linear-48B-A3B-Instruct`` ``config.json``.
For a row of tokens ``u`` with segment ids ``s`` (documents are contiguous and
their ids differ); layers are numbered from 1 as ``linear_attn_config``
numbers them::

    x = E[u]
    layer i:  x += mixer_i(rms(x));  x += FFN_i(rms(x))
    KDA:        q, k, v = silu(conv4(a W_q)), silu(conv4(a W_k)),
                silu(conv4(a W_v))      # depthwise causal, 4 taps, no bias,
                                        # a tap that would reach another
                                        # document reads 0
                a head at a time  q = q / |q|_2 / sqrt(d_k),  k = k / |k|_2
                g = -exp(A_log_h) softplus(W_fb (W_fa a) + dt_bias)
                                        # float32, one number a channel of
                                        # every head's key; alpha = exp(g)
                beta = sigmoid(a W_b)   # one number a head
                S = 0 at every document's first token, then
                S' = Diag(alpha_t) S_{t-1}
                S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;  o_t = S_t^T q_t
                out = W_o (rms_head(o) * sigmoid(W_gb (W_ga a)))
    attention:  q = a W_q (no latent);  [c ; k_pe] = a W_kva;
                [k_nope ; v] a head = rms(c) W_kvb;  a head's key is
                [k_nope ; k_pe] (k_pe shared by the heads), wider than its
                value;  no rotation of any part
                o = softmax(q k^T / sqrt(d_qk), mask j <= i and s_j == s_i) v
                out = concat(o) W_o
    FFN:        the first ``first_k_dense_replace`` layers SwiGLU of width
                ``intermediate_size``; every later one
                sc = sigmoid(h W_r) in float32;  chosen = top-k of (sc + b_i)
                g_e = scaling * sc_e / sum over chosen of sc
                y = Shared(h) + sum over e chosen and held here of
                    g_e Expert_e(h)                        (SwiGLU both)
    head:       logits = rms(x) W_head (untied);  loss = mean CE(logits_t,
                u_{t+1}) over t with s_{t+1} == s_t
    every expert layer, once a step:  c_e = tokens that chose e;
                b_e += bias_update_speed * sign(mean(c) - c_e)

The recurrence is computed in chunks of ``Config.kda_chunk`` tokens
(:func:`kda_scan`), every product a matrix product, equal to the recurrence
up to rounding.  One algorithm, two executions
(:func:`kda_scan_runs_fused`; :func:`kda_scan` describes both): on a TPU, at
shapes that fill its tiles (the published 32 heads of 128 x 128 at chunks of
64), the two Pallas kernels of ``kda_pallas``, everywhere else ``jnp`` code
with a backward pass of its own, which is the kernels' oracle in the tests.
A step counts which ran
(``kda_scan_fused_steps_total`` / ``kda_scan_plain_steps_total``).  In both
a layer's recomputation keeps by name what the scan holds between its
passes (``SAVED``), so the recurrence runs forward once a step.

What is this model's own is the KDA mixer.  The norm, the products, the
convolution, the latent attention, the blocked attention and the blocked
loss are ``packed_rows``'s, the routed layer and the routing state (the
``moe`` collection) ``parallel/moe.py``'s, the layer loop, the feed-forward
half of a layer and the registry's surface ``packed_decoder``'s, whose
docstring says what holds for every such decoder (``Config.experts_held``
among it); here the decay, the norms, the router and the state's carry are
float32.  The published keys of 192 a head are not whole rows of 128 lanes
and the values are narrower than the keys, so attention runs as ``jnp`` code
on every backend (``packed_rows.attention_runs_fused``) and a step says so
(``attention_plain_steps_total``).

``jax.named_scope`` names a device trace can be cut by: ``kda_mixer`` (the
norm and the mixer whole) > ``kda_project`` (q, k, v, the decay's, beta's and
the gate's projections), ``kda_conv`` (the three convolutions and SiLU),
``kda_scan`` (the L2 norms, the decay, the chunked recurrence and its
hand-over between chunks: the kernels' calls, forward and backward, carry
it and ``kda_mixer`` in their ``op_name``), ``kda_out`` (the heads' norm, the gate, ``W_o``);
``attention`` > ``mla_project``; ``mlp``; ``shared_expert``; ``moe_router``,
``moe_dispatch``, ``moe_experts``, ``moe_combine`` (``routed_experts``');
``lm_head``.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from tensorflowonspark_tpu.models import packed_decoder
from tensorflowonspark_tpu.models.kernels import runs_fused, step_counters
from tensorflowonspark_tpu.models.packed_rows import (
    block, causal_conv, latent_attention, mm, rms, row_counters, under)

#: no sequence-parallel sharding: the state has no hand-over across ``sp`` yet
SEQUENCE_AXES: dict = {}

#: the recipe :func:`make_optimizer` builds (a continued-pre-training AdamW)
ADAMW = {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1}

#: the collection of non-gradient state (``packed_decoder.COLLECTION``)
COLLECTION = packed_decoder.COLLECTION

#: the published pattern, layers numbered from 1: ``K K K A`` six times, then
#: ``K K A``
PUBLISHED_KDA = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22,
                 23, 25, 26)
PUBLISHED_FULL = (4, 8, 12, 16, 20, 24, 27)

#: under the root of a query's and a key's L2 norm (the public implementation)
L2_EPS = 1e-6

#: the largest exponent :func:`kda_scan` takes of a key's factor inside its own
#: sub-block (``exp(88.7)`` is the last float32)
EXPONENT_CAP = 80.0

#: chunks the ``jnp`` form of :func:`kda_scan` takes at a time (256 tokens of
#: the published 64): a group's working set is what its backward pass holds
#: at once, and on a v5e the backward pass of a layer's scan takes 18.4 ms at
#: 2 and at 4 chunks a group, 22.0 at 8 and 29.6 at 16 (PERF.md section 6,
#: PR 43)
SCAN_GROUP = 4

#: what :func:`kda_scan` names for a caller's ``jax.checkpoint`` to keep: its
#: outputs, the state entering each group of chunks (the kernels: each pair)
#: and, of the kernels alone, the chunks' inverses
SAVED = ("kda_scan_out", "kda_scan_states", "kda_scan_inverse")


@dataclasses.dataclass(frozen=True)
class Config:
    vocab_size: int = 163840        # rows of the vocabulary held here
    hidden_size: int = 2304
    num_hidden_layers: int = 27     # layers 1..n of the two lists below run
    kda_layers: tuple = PUBLISHED_KDA           # ``linear_attn_config``'s
    full_attn_layers: tuple = PUBLISHED_FULL    # lists, numbered from 1
    kda_num_heads: int = 32         # ``linear_attn_config.num_heads``
    kda_head_dim: int = 128         # ``linear_attn_config.head_dim``
    short_conv_kernel_size: int = 4
    first_k_dense_replace: int = 1
    intermediate_size: int = 9216   # the dense layers' SwiGLU
    moe_intermediate_size: int = 1024
    num_experts: int = 256          # the router's width
    experts_held: tuple = tuple(range(256))
    num_shared_experts: int = 1
    num_experts_per_token: int = 8
    routed_scaling_factor: float = 2.446
    moe_renormalize: bool = True
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64      # shared by the heads; not rotated (NoPE)
    v_head_dim: int = 128
    rms_norm_eps: float = 1e-5
    bias_update_speed: float = 0.001
    init_std: float = 0.02
    dtype: str = "bfloat16"
    seq_len: int = 8192             # tokens a packed row
    kda_chunk: int = 64             # tokens the recurrence takes at a time
    attention_block: int = 256      # queries scored at a time
    loss_block: int = 2048          # tokens whose logits are held at a time

    def __post_init__(self):
        run = range(1, self.num_hidden_layers + 1)
        kinds = [(i in self.kda_layers) + (i in self.full_attn_layers)
                 for i in run]
        if any(k != 1 for k in kinds):
            raise ValueError("every layer run is in kda_layers or in "
                             f"full_attn_layers, and in one: {list(run)}")

    @classmethod
    def tiny(cls) -> "Config":
        return cls(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                   kda_layers=(1,), full_attn_layers=(2,),
                   kda_num_heads=2, kda_head_dim=8, intermediate_size=64,
                   moe_intermediate_size=16, num_experts=16,
                   experts_held=(2, 3), num_experts_per_token=3,
                   num_attention_heads=2, kv_lora_rank=8,
                   qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
                   dtype="float32", seq_len=32, kda_chunk=8,
                   attention_block=16, loss_block=16)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def kda_width(self) -> int:
        """Channels of a KDA layer's queries, keys and values."""
        return self.kda_num_heads * self.kda_head_dim

    @property
    def expert_layers(self) -> int:
        """Layers with a router."""
        return max(self.num_hidden_layers - self.first_k_dense_replace, 0)


def layer_kinds(config: Config) -> list:
    """``(prefix, mixer, ffn)`` of every layer run, in forward order:
    ``mixer`` is ``"kda"`` or ``"full_attention"``, ``ffn`` ``"dense"`` or
    ``"experts"``.  Prefixes count from 0, the published lists from 1."""
    return [(f"l{i:02d}_",
             "kda" if i + 1 in config.kda_layers else "full_attention",
             "dense" if i < config.first_k_dense_replace else "experts")
            for i in range(config.num_hidden_layers)]


def leaf_shapes(config: Config) -> dict:
    """Name -> shape of every parameter, in forward order."""
    d, heads = config.hidden_size, config.num_attention_heads
    p, hd, kh = config.kda_width, config.kda_head_dim, config.kda_num_heads
    f = config.moe_intermediate_size
    out = {"embed": (config.vocab_size, d)}
    for pre, mixer, ffn in layer_kinds(config):
        out[pre + "norm1"] = (d,)
        if mixer == "kda":
            for name in ("q", "k", "v"):
                out[pre + f"kda_{name}"] = (d, p)
            for name in ("q", "k", "v"):
                out[pre + f"kda_{name}_conv"] = (
                    config.short_conv_kernel_size, p)
            out[pre + "kda_f_a"] = (d, hd)      # the decay's low rank
            out[pre + "kda_f_b"] = (hd, p)
            out[pre + "kda_dt_bias"] = (p,)
            out[pre + "kda_A_log"] = (kh,)
            out[pre + "kda_beta"] = (d, kh)
            out[pre + "kda_g_a"] = (d, hd)      # the output gate's
            out[pre + "kda_g_b"] = (hd, p)
            out[pre + "kda_o_norm"] = (hd,)
            out[pre + "kda_wo"] = (p, d)
        else:
            out[pre + "wq"] = (d, heads * config.qk_head_dim)
            out[pre + "kv_a"] = (d, config.kv_lora_rank
                                 + config.qk_rope_head_dim)
            out[pre + "kv_a_norm"] = (config.kv_lora_rank,)
            out[pre + "kv_b"] = (config.kv_lora_rank, heads * (
                config.qk_nope_head_dim + config.v_head_dim))
            out[pre + "wo"] = (heads * config.v_head_dim, d)
        out[pre + "norm2"] = (d,)
        out.update(packed_decoder.ffn_leaf_shapes(
            pre, ffn, d, config.intermediate_size, f, routing(config),
            shared=f * config.num_shared_experts))
    out["final_norm"] = (d,)
    out["head"] = (config.vocab_size, d)
    return out


def routing(config: Config):
    """This layout's routed layers, as ``parallel/moe.py`` names them."""
    from tensorflowonspark_tpu.parallel import moe

    return moe.Routing(
        n_experts=config.num_experts, layers=config.expert_layers,
        held=config.experts_held, top_k=config.num_experts_per_token,
        scale=config.routed_scaling_factor,
        normalize=config.moe_renormalize, speed=config.bias_update_speed)


# ---------------------------------------------------------------------------
# The mathematics, over the flat parameter dict
# ---------------------------------------------------------------------------


def sub_block(chunk: int) -> int:
    """Tokens of a sub-block of :func:`kda_scan`: a quarter of the chunk
    (16 of 64, as the public kernels), the whole chunk where it has no
    quarter."""
    return chunk // 4 if chunk % 4 == 0 else chunk


def unit_lower_inverse(a, sub: int):
    """``(I + a)^-1`` of strictly lower triangular ``a`` (..., C, C),
    float32, ``C`` whole blocks of ``sub``: the diagonal blocks by forward
    substitution, a row at a time and all blocks at once (``sub`` small
    steps), then twice as large a block at a time from ``[[P, 0], [R, Q]]^-1
    = [[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]``, every product a matrix product
    at the highest precision.  (No Neumann series: the powers of ``a`` of a
    document that repeats one token grow past what float32 can cancel.)"""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    lead, c = a.shape[:-2], a.shape[-1]
    n = c // sub
    tiles = a.reshape(lead + (n, sub, n, sub))
    diag = jnp.stack([tiles[..., i, :, i, :] for i in range(n)], axis=-3)
    eye = jnp.eye(sub, dtype=a.dtype)
    inv = jnp.broadcast_to(eye, diag.shape)     # a row is filled at a time
    for i in range(1, sub):
        inv = inv.at[..., i, :].set(eye[i] - jnp.einsum(
            "...j,...jc->...c", diag[..., i, :], inv, precision=hi))
    while n > 1:
        tiles = a.reshape(lead + (n // 2, 2, sub, n // 2, 2, sub))
        under = jnp.stack([tiles[..., i, 1, :, i, 0, :]
                           for i in range(n // 2)], axis=-3)
        pairs = inv.reshape(lead + (n // 2, 2, sub, sub))
        first, second = pairs[..., 0, :, :], pairs[..., 1, :, :]
        corner = -jnp.einsum("...ij,...jk->...ik", second, jnp.einsum(
            "...ij,...jk->...ik", under, first, precision=hi), precision=hi)
        inv = jnp.concatenate([
            jnp.concatenate([first, jnp.zeros_like(first)], axis=-1),
            jnp.concatenate([corner, second], axis=-1)], axis=-2)
        n, sub = n // 2, 2 * sub
    return inv.reshape(lead + (c, c))


def _group(chunk: int, dtype, state, inp):
    """``SCAN_GROUP`` chunks of :func:`kda_scan`: the state entering them
    (H, K, V) float32 and their operands (size, C, H, X), the documents'
    indices (size, C), the last token's (size,) and the one of the chunk
    before's -> the state leaving them and their outputs (size, C, H, V)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    qg, kg, vg, gg, bg, doc, last, before = inp
    size, _, heads, dk = qg.shape
    sub = sub_block(chunk)
    ns = chunk // sub
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    sees = (doc == before[:, None])[:, None, :, None]   # reads S_0
    same = doc[:, :, None] == doc[:, None, :]
    upto = (same & lower)[:, None]                      # r <= t, one document
    below = (same & jnp.tril(lower, -1))[:, None]       # r < t

    def chunks(a):          # (size, C, H, X) -> (size, H, C, X)
        return a.transpose(0, 2, 1, 3)

    qc, kc, vc = chunks(qg).astype(f32), chunks(kg).astype(f32), \
        chunks(vg).astype(f32)
    bc = chunks(bg[..., None]).astype(f32)              # (size, H, C, 1)
    gsum = jnp.einsum("tr,nhrk->nhtk", lower.astype(f32),
                      chunks(gg).astype(f32),
                      precision=jax.lax.Precision.HIGHEST)          # G

    # the pairwise terms, a row's sub-block at a time
    ref = gsum[:, :, ::sub]                             # (size, H, ns, K)
    to_row = jnp.exp(gsum - jnp.repeat(ref, sub, axis=2))
    keys = (kc[:, :, None] * jnp.exp(jnp.minimum(
        ref[:, :, :, None] - gsum[:, :, None], EXPONENT_CAP))).astype(dtype)

    def blocks(rows):       # (size, H, C, K) -> (size, H, ns, sub, K)
        return (rows * to_row).astype(dtype).reshape(size, heads, ns, sub, dk)

    # sum_c rows_tc k_rc exp(G_tc - G_rc), the queries' rows and the keys'
    # against the same keys in one product
    both = jnp.einsum(
        "nhisk,nhirk->nhisr",
        jnp.concatenate([blocks(qc), blocks(kc)], axis=3), keys,
        preferred_element_type=f32)
    q_k = jnp.where(upto, both[:, :, :, :sub].reshape(
        size, heads, chunk, chunk), 0.0).astype(dtype)
    a = jnp.where(below, both[:, :, :, sub:].reshape(
        size, heads, chunk, chunk), 0.0) * bc
    decayed = jnp.exp(gsum)
    inverse = unit_lower_inverse(a, sub).astype(dtype)
    u0 = mm("nhtr,nhrv->nhtv", inverse, bc * vc, dtype, out=f32)
    w = mm("nhtr,nhrk->nhtk", inverse,
           jnp.where(sees, bc * kc * decayed, 0.0), dtype)

    # what a chunk hands on: its tokens of the last token's document,
    # decayed to the chunk's end; the entering state goes through if no
    # document began in the chunk
    to_end = jnp.where((doc == last[:, None])[:, None, :, None],
                       kc * jnp.exp(gsum[:, :, -1:] - gsum), 0.0
                       ).astype(dtype)
    through = jnp.where((last == before)[:, None, None], decayed[:, :, -1],
                        0.0)

    def cross(state, inp):
        w_n, u_n, k_n, keep = inp
        u = (u_n - mm("hck,hkv->hcv", w_n, state, dtype, out=f32)
             ).astype(dtype)
        return (state * keep[..., None]
                + mm("hck,hcv->hkv", k_n, u, dtype, out=f32)), (
                    state.astype(dtype), u)

    state, (entering, u) = jax.lax.scan(cross, state,
                                        (w, u0, to_end, through))
    o = (mm("nhck,nhkv->nhcv",
            jnp.where(sees, qc * decayed, 0.0).astype(dtype), entering,
            dtype, out=f32)
         + mm("nhcr,nhrv->nhcv", q_k, u, dtype, out=f32))
    return state, o.transpose(0, 2, 1, 3).astype(dtype)


@functools.lru_cache(maxsize=None)
def _grouped_rule(chunk: int, dtype, scopes: tuple):
    """:func:`kda_scan`'s recurrence over its groups of chunks with its own
    backward pass (made once a chunk, type and scopes: the module imports
    JAX only when it is used)."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name

    group = functools.partial(_group, chunk, dtype)

    def empty(xs):
        (_, _, _, heads, dk), dv = xs[0].shape, xs[2].shape[-1]
        return jnp.zeros((heads, dk, dv), jnp.float32)

    def fwd(*xs):
        def step(state, inp):
            new, o = group(state, inp)
            return new, (o, state)

        _, (o, entering) = jax.lax.scan(step, empty(xs), xs)
        return (checkpoint_name(o, SAVED[0]),
                (xs, checkpoint_name(entering, SAVED[1])))

    def bwd(saved, d_o):
        xs, entering = saved

        def step(d_state, inp):
            x, state, d_og = inp
            _, vjp = jax.vjp(lambda s, *ops: group(s, ops + x[5:]), state,
                             *x[:5])
            d_state, *d_ops = vjp((d_state, d_og))
            return d_state, tuple(d_ops)

        with under(scopes):
            _, d_ops = jax.lax.scan(step, empty(xs), (xs, entering, d_o),
                                    reverse=True)
        return d_ops + tuple(np.zeros(a.shape, jax.dtypes.float0)
                             for a in xs[5:])

    rule = jax.custom_vjp(lambda *xs: fwd(*xs)[0])
    rule.defvjp(fwd, bwd)
    return rule


def kda_scan_runs_fused(chunk: int, heads: int, dk: int, dv: int) -> bool:
    """Whether :func:`kda_scan` runs on the kernels of ``kda_pallas`` at
    chunks of ``chunk`` tokens and ``heads`` heads with keys of ``dk`` and
    values of ``dv``: ``kernels.runs_fused`` of ``kda_pallas.fits`` (the
    published 32 x 128 x 128 at chunks of 64 do; ``Config.tiny()``'s do
    not)."""
    from tensorflowonspark_tpu.models import kda_pallas

    return runs_fused(kda_pallas, chunk, heads, dk, dv)


def kda_scan(q, k, v, g, beta, seg, chunk: int, dtype, scopes: tuple = ()):
    """The gated delta rule of one packed row in chunks: ``S' = Diag(exp
    g_t) S_{t-1}``, ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``, ``o_t =
    S_t^T q_t``, with ``S = 0`` entering a document.

    ``q`` and ``k`` (T, H, K), ``v`` (T, H, V), ``g`` (T, H, K) float32 and
    never positive, ``beta`` (T, H) float32, ``seg`` (T,).  Inside a chunk of
    ``chunk`` tokens, with ``G`` the running sum of ``g`` and ``S_0`` the
    state entering it::

        A[t, r] = beta_t sum_c k_tc k_rc exp(G_tc - G_rc)        (r < t)
        (I + A) U = Diag(beta) (V - (K * exp G) S_0)   # unit lower triangle
        o_t = (q_t * exp G_t) S_0 + sum_{r <= t} [sum_c q_tc k_rc
                                                  exp(G_tc - G_rc)] u_r
        S_C = Diag(exp G_C) S_0 + sum_r (k_r * exp(G_C - G_r)) u_r^T

    a pair of tokens of different documents contributing nothing and a token
    behind a document's first seeing no ``S_0``.  Every exponent above is a
    sum of ``g`` over a stretch of tokens and so never positive; the program
    keeps it so where it can: a pairwise term is made as a product of two
    factors relative to the first token of the row's sub-block
    (:func:`sub_block`), ``exp(G_t - G_ref)`` and ``exp(G_ref - G_r)``, which
    are both decays for every key of an earlier sub-block.  For a key of the
    row's own sub-block the second factor grows, by what at most 15 tokens
    decay (at the published initialisation ``g`` reaches -1.6 a token:
    ``exp(24)``); it is taken of at most ``EXPONENT_CAP``, so a channel that
    loses more than ``exp(-80)`` inside one sub-block (5.3 a token, held for
    all of it) forgets that much and no more there, and nothing overflows at
    any decay.  Never ``exp(G) exp(-G)`` over a chunk: ``G`` reaches -100.

    The solve (the inverse, then one product) gives ``U = U_0 - W S_0``
    (both at once), so the state crosses a chunk in two products; ``G`` is a
    product with a triangle of ones.  Products take operands in ``dtype``
    and accumulate in float32, the running sums, the inverse and the
    state's carry are float32 at the highest precision.

    One algorithm, two executions (:func:`kda_scan_runs_fused`).  On a TPU
    at shapes that fill its tiles, ``kda_pallas.fused_scan``: a forward and
    a backward kernel over (block of heads, pair of chunks) cells, the
    chunks in turn with the state in VMEM scratch, nothing of a chunk's own
    in HBM; between the passes it holds the state entering each pair of
    chunks and the chunks' inverses, in ``dtype``.  Elsewhere the ``jnp``
    form below: the row is taken ``SCAN_GROUP`` chunks at a time (a
    ``lax.scan`` whose carry is the state): a group's pairwise terms and
    solves (:func:`unit_lower_inverse`) are made for all its chunks at once,
    the state crosses its chunks in an inner ``lax.scan``, the outputs are
    made from the states that leaves; its backward pass
    (:func:`_grouped_rule`) makes a group again from the state that entered
    it and differentiates that, last group first, so what is held between
    the two passes is a state a group.  Either backward pass runs under the
    ``jax.named_scope``s ``scopes`` (the caller's: the forward pass runs
    under the caller's own), and what either holds between its passes is
    named (``SAVED``): a caller that recomputes its layer keeps it by name
    and does not run the recurrence a second time.  ``T`` need not be a
    multiple of ``chunk``: the row is padded with a document of its own.
    Returns (T, H, V) in ``dtype``."""
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import kda_pallas

    t, heads, _ = q.shape
    fused = kda_scan_runs_fused(chunk, heads, q.shape[-1], v.shape[-1])
    pad = (-t) % (kda_pallas.CELL if fused else chunk)
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            for a in (q, k, v, g, beta))
        seg = jnp.pad(seg, (0, pad), constant_values=-1)
    nc = (t + pad) // chunk

    # documents by their index in the row (from 1), chunk by chunk
    first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    doc = jnp.cumsum(first.astype(jnp.int32)).reshape(nc, chunk)
    last = doc[:, -1]
    before = jnp.concatenate([jnp.zeros((1,), jnp.int32), last[:-1]])
    if fused:
        return kda_pallas.fused_scan(q, k, v, g, beta, doc, last, before,
                                     dtype, SAVED, scopes)[:t]
    size = block(nc, SCAN_GROUP)

    def groups(a):          # (nc, ...) -> (groups, size, ...)
        return a.reshape((nc // size, size) + a.shape[1:])

    o = _grouped_rule(chunk, jnp.dtype(dtype), tuple(scopes))(
        *(groups(a.reshape((nc, chunk) + a.shape[1:]))
          for a in (q, k, v, g, beta)), groups(doc), groups(last),
        groups(before))
    return o.reshape(t + pad, heads, o.shape[-1])[:t]


def _l2(x):
    """``x`` over its last axis' L2 norm, float32."""
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + L2_EPS)


def kda_mixer(params, prefix: str, h, seg, config: Config):
    """Kimi Delta Attention on one row: ``h`` (T, D) -> (T, D).  The
    convolutions, the L2 norms, the decay, the step size and the heads' norm
    are float32 between the products."""
    import jax
    import jax.numpy as jnp

    f32, dtype, t = jnp.float32, h.dtype, h.shape[0]
    heads, hd = config.kda_num_heads, config.kda_head_dim
    pre = prefix + "kda_"
    with jax.named_scope("kda_project"):
        qkv = [mm("td,de->te", h, params[pre + name], dtype)
               for name in ("q", "k", "v")]
        decay = mm("tr,re->te", mm("td,dr->tr", h, params[pre + "f_a"], dtype),
                   params[pre + "f_b"], dtype, out=f32)
        beta = jax.nn.sigmoid(mm("td,dh->th", h, params[pre + "beta"], dtype,
                                 out=f32))
        gate = mm("tr,re->te", mm("td,dr->tr", h, params[pre + "g_a"], dtype),
                  params[pre + "g_b"], dtype)
    with jax.named_scope("kda_conv"):
        # q and k go on to their L2 norms in float32; v to the products
        q, k, v = (causal_conv(
            x, params[pre + f"{name}_conv"], 0.0, seg, silu=True, out=out,
            scopes=("kda_mixer", "kda_conv")).reshape(t, heads, hd)
                   for x, name, out in zip(qkv, "qkv", (f32, f32, dtype)))
    with jax.named_scope("kda_scan"):
        g = -jnp.exp(params[pre + "A_log"])[:, None] * jax.nn.softplus(
            decay.reshape(t, heads, hd)
            + params[pre + "dt_bias"].reshape(heads, hd))
        o = kda_scan((_l2(q) * hd ** -0.5).astype(dtype),
                     _l2(k).astype(dtype), v, g, beta, seg,
                     config.kda_chunk, dtype, ("kda_mixer", "kda_scan"))
    with jax.named_scope("kda_out"):
        o = rms(o, params[pre + "o_norm"], config.rms_norm_eps)
        y = (o.reshape(t, heads * hd)
             * jax.nn.sigmoid(gate.astype(f32))).astype(dtype)
        return mm("te,ed->td", y, params[pre + "wo"], dtype)


def attention(params, prefix: str, h, seg, config: Config):
    """``packed_rows.latent_attention`` at this layout's sizes: no query
    latent, no rotation, values narrower than keys."""
    return latent_attention(
        params, prefix, h, seg, None, heads=config.num_attention_heads,
        nope=config.qk_nope_head_dim, rope_dim=config.qk_rope_head_dim,
        v_dim=config.v_head_dim, kv_rank=config.kv_lora_rank,
        eps=config.rms_norm_eps,
        size=block(h.shape[0], config.attention_block))


def _layer(mixer: str, ffn: str, prefix: str, config: Config, scopes: tuple,
           lp, x, seg, pos, bias):
    """One layer on a batch of rows: ``x`` (B, T, D) -> ``(x, counts)``;
    ``counts`` is (E,) zeros for a dense layer (``pos`` is not read: no
    layer has a positional encoding)."""
    import jax

    eps = config.rms_norm_eps
    if mixer == "kda":
        scope, mix = "kda_mixer", lambda hr, sr: kda_mixer(
            lp, prefix, hr, sr, config)
    else:
        scope, mix = "attention", lambda hr, sr: attention(
            lp, prefix, hr, sr, config)
    with jax.named_scope(scope):
        x = x + jax.vmap(mix)(rms(x, lp[prefix + "norm1"], eps), seg)
    return packed_decoder.feed_forward(
        lp, prefix, ffn, x, bias, eps, routing(config), shared=True,
        scopes=scopes)


def logits(params, x, config: Config):
    """The untied head on states ``x`` (N, D): float32 (N, V)."""
    import jax.numpy as jnp

    h = rms(x, params["final_norm"], config.rms_norm_eps)
    return mm("td,vd->tv", h, params["head"], h.dtype, out=jnp.float32)


# ---------------------------------------------------------------------------
# The zoo's surface
# ---------------------------------------------------------------------------


def _init(config: Config):
    """``(name, shape) ->`` a leaf's initializer: unit norms, normal
    matrices, the taps as PyTorch's ``Conv1d`` leaves them, ``A_log`` and
    ``dt_bias`` as the public code and Mamba-2 draw them."""
    import flax.linen as nn

    normal, out = packed_decoder.normals(config.init_std,
                                         config.num_hidden_layers)
    taps = packed_decoder.conv_taps(config.short_conv_kernel_size)

    def init(name, shape):
        if name.endswith("_A_log"):
            return packed_decoder.a_log
        if name.endswith("_dt_bias"):
            return packed_decoder.dt_bias
        if len(shape) == 1:
            return nn.initializers.ones
        if name.endswith("_conv"):
            return taps
        return out if name.endswith(("_wo", "_down")) else normal

    return init


#: a layer is made again in the backward pass, but for what its recurrence
#: names (``SAVED``): the scan is not run a second time for them
_DECODER = packed_decoder.Decoder(
    adamw=ADAMW, leaf_shapes=leaf_shapes, layers=layer_kinds, layer=_layer,
    logits=logits, init=_init, routing=routing, saved=SAVED)
collection_shapes = _DECODER.collection_shapes
hidden_states = _DECODER.hidden_states
apply_tokens = _DECODER.apply_tokens
loss_terms = _DECODER.next_token_terms
make_model = _DECODER.make_model
make_optimizer = _DECODER.make_optimizer
make_loss_fn = _DECODER.make_loss_fn
make_forward_fn = _DECODER.make_forward_fn
device_counters = _DECODER.device_counters
counter_rows = _DECODER.counter_rows
parameter_count = _DECODER.parameter_count
example_batch = _DECODER.example_batch


def batch_counters(batch, config: Config) -> dict:
    """What one step adds to the program's counters
    (``packed_rows.row_counters``: the host batch's tokens, loss tokens and
    documents, and which executions of attention and of the mixers'
    convolutions its trace applied; ``moe.grouped_step_counters``: which
    execution of the routed experts' grouped products; the chunks
    :func:`kda_scan` took: chunks a row x rows x heads x KDA layers; and, by
    the same kind of rule (:func:`kda_scan_runs_fused`), one step of the
    recurrence on the kernels or as ``jnp`` code, the other named with 0)."""
    from tensorflowonspark_tpu.parallel import moe

    seg = np.asarray(batch["segment_ids"])
    mixers = [mixer for _, mixer, _ in layer_kinds(config)]
    scans = "kda" in mixers
    return {**row_counters(seg, config.qk_head_dim,
                           (None,) * mixers.count("full_attention"),
                           config.v_head_dim,
                           conv=(config.kda_width,
                                 config.short_conv_kernel_size)
                           if scans else None),
            **moe.grouped_step_counters(
                seg.size, routing(config), config.hidden_size,
                config.moe_intermediate_size, config.dtype),
            "kda_chunks_total": int(
                seg.shape[0] * -(-seg.shape[1] // config.kda_chunk)
                * config.kda_num_heads * mixers.count("kda")),
            **step_counters("kda_scan", kda_scan_runs_fused(
                config.kda_chunk, config.kda_num_heads, config.kda_head_dim,
                config.kda_head_dim), scans)}
