"""BERT encoder + SQuAD span head — acceptance config #5 (``BASELINE.md``)
and the flagship model of the framework (``__graft_entry__.py``).

Reference anchor: **no BERT exists in the reference** — config #5 comes from
``BASELINE.json::configs`` ("BERT-base SQuAD fine-tune streamed from Spark
DataFrame, sharded over TPU pod").  The design is TPU-native throughout:

- bfloat16 activations, float32 layernorm/softmax/loss.
- QKV projected in ONE fused dense (3·H) — one big MXU matmul, not three.
- attention runs through :mod:`tensorflowonspark_tpu.parallel.ring_attention`
  when the mesh has ``sp > 1`` (sequence sharded over ICI neighbours —
  long-context first-class), dense masked attention otherwise.
- params carry flax logical axes (``embed``/``heads``/``kv``/``mlp``/
  ``vocab``) so the one mesh maps DP/FSDP/TP/SP without model changes.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from tensorflowonspark_tpu.models import _common


@dataclasses.dataclass(frozen=True)
class Config:
    vocab_size: int = 30522
    hidden: int = 768
    layers: int = 12
    heads: int = 12
    mlp_dim: int = 3072
    max_len: int = 512
    type_vocab: int = 2
    dtype: str = "bfloat16"
    remat: bool = False  # jax.checkpoint each layer: FLOPs for HBM
    # sequence-parallel attention implementation when the mesh has sp > 1:
    # "ring" (K/V ppermute, O(seq/sp) memory — long-context default) or
    # "ulysses" (all_to_all head re-shard; needs local heads % sp == 0)
    sp_impl: str = "ring"
    # Mixture-of-Experts (parallel/moe.py): > 0 replaces the dense MLP of
    # every ``moe_every``-th layer with ``moe_experts`` expert FFNs,
    # expert-parallel over the mesh's ``ep`` axis (Switch top-1 routing,
    # load-balance aux loss weighted ``moe_aux_weight``).  Layered trunk
    # only (combine with dp/fsdp/tp/sp; not with pp_stages).
    moe_experts: int = 0
    moe_every: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # routing-group size in tokens: capacity + aux apply per group, and the
    # dispatch tensors stay linear in global tokens (moe.moe_ffn)
    moe_group_size: int = 1024
    # pipeline parallelism: > 1 switches the encoder trunk to STACKED layer
    # params (leading "stage" dim sharded over pp) run as a GPipe microbatch
    # schedule when the mesh has that many pp ranks, a lax.scan otherwise
    # (parallel/pipeline_parallel.py).  layers % pp_stages must be 0.
    pp_stages: int = 0
    pp_microbatches: int = 4

    @classmethod
    def tiny(cls) -> "Config":
        return cls(vocab_size=128, hidden=32, layers=2, heads=4, mlp_dim=64,
                   max_len=64, dtype="float32")

    @classmethod
    def large(cls) -> "Config":
        return cls(hidden=1024, layers=24, heads=16, mlp_dim=4096)

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads


#: sequence axis of each batch leaf (sharded over ``sp`` when sp > 1)
SEQUENCE_AXES = {"input_ids": 1, "token_type_ids": 1, "attention_mask": 1}


def make_model(config: Config, mesh=None):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(config.dtype)
    use_ring = mesh is not None and mesh.shape.get("sp", 1) > 1
    if use_ring:
        from tensorflowonspark_tpu.parallel import ring_attention as ra

        sharded_attn = ra.make_sharded_attention(mesh, causal=False,
                                                 impl=config.sp_impl)

    class Dense(nn.Module):
        """``nn.DenseGeneral`` over the last axis, kernel boxed with ``axes``.

        flax's own unboxes what its initializer returns under whatever mesh
        is current, with the names as a sharding constraint; inside the
        bucketed step's ``shard_map`` region that mesh is all ``Manual`` and
        logical names are none of its axes.  ``self.param`` leaves the box
        to ``parallel.mesh.param_sharding_from_metadata``."""
        features: tuple
        axes: tuple

        @nn.compact
        def __call__(self, x):
            kernel = self.param(
                "kernel",
                nn.with_partitioning(
                    nn.initializers.normal(stddev=0.02), self.axes),
                (x.shape[-1],) + self.features, jnp.float32)
            bias = self.param("bias", nn.initializers.zeros_init(),
                              self.features, jnp.float32)
            x, kernel, bias = nn.dtypes.promote_dtype(
                x, kernel, bias, dtype=dtype)
            return jax.lax.dot_general(
                x, kernel, (((x.ndim - 1,), (0,)), ((), ()))) + bias

    def dense(features, axes, name=None):
        if isinstance(features, int):
            features = (features,)
        return Dense(features, axes, name=name)

    class Attention(nn.Module):
        @nn.compact
        def __call__(self, x, mask):
            b, s, _ = x.shape
            h, d = config.heads, config.head_dim
            qkv = dense((3, h, d), ("embed", None, "heads", "kv"), name="qkv")(x)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # (B,S,H,D)
            if use_ring:
                # sequence is sharded over sp: K/V blocks ring over ICI,
                # the key-padding mask rides along with its block
                o = sharded_attn(q, k, v, kv_mask=mask)
            else:
                scale = 1.0 / math.sqrt(d)
                # scores on the MXU: bf16 multiply, f32 accumulate
                # (preferred_element_type) — an explicit f32 upcast here
                # risks the chip's slow multi-pass f32 matmul path
                s_ = jnp.einsum(
                    "bqhd,bkhd->bhqk", q.astype(dtype), k.astype(dtype),
                    preferred_element_type=jnp.float32,
                ) * scale
                s_ = jnp.where(mask[:, None, None, :], s_, -1e30)
                p = jax.nn.softmax(s_, axis=-1)
                o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(dtype), v,
                               preferred_element_type=jnp.float32
                               ).astype(dtype)
            o = o.reshape(b, s, h * d)
            return dense(config.hidden, ("heads", "embed"), name="out")(o)

    class MoEMLP(nn.Module):
        """Expert-parallel FFN (Switch top-1) — see ``parallel/moe.py``.
        Returns ``(y, aux_loss)``; the caller threads aux functionally so
        init/inference stay collection-free.  ``mask`` (B, S) keeps padding
        tokens out of the router: they'd otherwise claim expert capacity
        ahead of later sequences' real tokens and skew the aux loss."""

        @nn.compact
        def __call__(self, x, mask):
            from tensorflowonspark_tpu.parallel import moe

            E, M, H = config.moe_experts, config.hidden, config.mlp_dim
            normal = nn.initializers.normal(stddev=0.02)
            zeros = nn.initializers.zeros_init()

            def par(name, shape, init):
                return self.param(
                    name, nn.with_partitioning(init, moe.PARAM_AXES[name]),
                    shape, jnp.float32)

            p = {
                "gate": par("gate", (M, E), normal),
                "w_in": par("w_in", (E, M, H), normal),
                "b_in": par("b_in", (E, H), zeros),
                "w_out": par("w_out", (E, H, M), normal),
                "b_out": par("b_out", (E, M), zeros),
            }
            return moe.moe_ffn(
                x, p, capacity_factor=config.moe_capacity_factor,
                token_mask=mask, group_size=config.moe_group_size)

    class Block(nn.Module):
        moe: bool = False

        @nn.compact
        def __call__(self, x, mask):
            y = Attention(name="attention")(x, mask)
            x = nn.LayerNorm(dtype=jnp.float32, name="ln_attn")(x + y).astype(dtype)
            if self.moe:
                y, aux = MoEMLP(name="moe_mlp")(x, mask)
            else:
                y = dense(config.mlp_dim, ("embed", "mlp"), name="mlp_in")(x)
                y = nn.gelu(y)
                y = dense(config.hidden, ("mlp", "embed"), name="mlp_out")(y)
                aux = jnp.zeros((), jnp.float32)
            x = nn.LayerNorm(dtype=jnp.float32, name="ln_mlp")(x + y).astype(dtype)
            return x, aux

    class Embeddings(nn.Module):
        @nn.compact
        def __call__(self, input_ids, token_type_ids):
            tok = self.param(
                "tok_embed",
                nn.with_partitioning(
                    nn.initializers.normal(stddev=0.02), ("vocab", "embed")
                ),
                (config.vocab_size, config.hidden), jnp.float32,
            )
            pos = self.param(
                "pos_embed",
                nn.with_partitioning(
                    nn.initializers.normal(stddev=0.02), (None, "embed")
                ),
                (config.max_len, config.hidden), jnp.float32,
            )
            typ = self.param(
                "type_embed",
                nn.with_partitioning(
                    nn.initializers.normal(stddev=0.02), (None, "embed")
                ),
                (config.type_vocab, config.hidden), jnp.float32,
            )
            s = input_ids.shape[1]
            x = (_common.embedding_lookup(tok, input_ids)
                 + pos[None, :s]
                 + _common.embedding_lookup(typ, token_type_ids))
            return nn.LayerNorm(
                dtype=jnp.float32, name="ln_embed")(x).astype(dtype)

    class StackedEncoder(nn.Module):
        """``config.layers`` post-LN blocks with STACKED parameters: every
        leaf carries a leading layer dim annotated ``"stage"`` (→ ``pp``).
        Executed as a GPipe pipeline (``parallel.pipeline_parallel``) when
        the mesh has ``pp == config.pp_stages`` ranks, as a ``lax.scan``
        otherwise — identical numerics either way (tested).

        **pp × sp composition**: the sequence stays sharded over ``sp``
        inside the pipeline (``pipeline_apply(seq_axis="sp")``) and each
        block runs :func:`parallel.ring_attention.ring_attention` directly
        over the bound ``sp`` axis — K/V blocks (and the key-padding mask)
        ``ppermute`` around the ring while microbatches flow through the
        GPipe stages, so long-context and pipelining compose
        (``tests/test_models.py::test_bert_pp_composes_with_sp_ring_attention``).

        **pp × tp composition**: qkv/out weights are head-major
        (``(L, H, 3, heads, head_dim)`` / ``(L, heads, head_dim, H)``) and
        the MLP ffn dim carries ``"mlp"``, so inside the pipeline's
        shard_map each tp rank holds ``heads/tp`` heads and ``mlp_dim/tp``
        ffn columns (``param_specs``), computes its partial attention/MLP
        output, and the block ``lax.psum``s the row-sharded matmul results
        over ``tp`` — Megatron-style TP inside each GPipe stage.  In the
        sequential (no-pp-mesh) path the same code runs global-view and
        GSPMD inserts the collectives from the storage shardings.

        Deliberately a functional twin of :class:`Block` rather than
        ``nn.scan(Block)``: nn.scan owns the execution (sequential) and
        hides its stacked params from ``pipeline_apply``, which needs them
        as a plain pytree to reshape into stages.  The two implementations
        are pinned to each other by
        ``tests/test_models.py::test_bert_stacked_encoder_matches_layered_block``
        (grafts layered weights into the stacked layout and compares
        forwards), so a drift in eps/masking/dtype policy fails loudly.
        """

        @nn.compact
        def __call__(self, x, mask):
            from jax.sharding import PartitionSpec as P

            from tensorflowonspark_tpu.parallel.pipeline_parallel import (
                pipeline_apply,
            )

            L, H = config.layers, config.hidden
            M, nh, hd = config.mlp_dim, config.heads, config.head_dim
            normal = nn.initializers.normal(stddev=0.02)
            zeros = nn.initializers.zeros_init()
            ones = nn.initializers.ones_init()

            def par(name, shape, axes, init):
                return self.param(
                    name, nn.with_partitioning(init, ("stage",) + axes),
                    (L,) + shape, jnp.float32,
                )

            w = {
                "qkv_w": par("qkv_w", (H, 3, nh, hd),
                             ("embed", None, "heads", "kv"), normal),
                "qkv_b": par("qkv_b", (3, nh, hd), (None, "heads", "kv"),
                             zeros),
                "out_w": par("out_w", (nh, hd, H), ("heads", "kv", "embed"),
                             normal),
                "out_b": par("out_b", (H,), (None,), zeros),
                "ln1_s": par("ln1_s", (H,), (None,), ones),
                "ln1_b": par("ln1_b", (H,), (None,), zeros),
                "mlp_in_w": par("mlp_in_w", (H, M), ("embed", "mlp"), normal),
                "mlp_in_b": par("mlp_in_b", (M,), ("mlp",), zeros),
                "mlp_out_w": par("mlp_out_w", (M, H), ("mlp", "embed"),
                                 normal),
                "mlp_out_b": par("mlp_out_b", (H,), (None,), zeros),
                "ln2_s": par("ln2_s", (H,), (None,), ones),
                "ln2_b": par("ln2_b", (H,), (None,), zeros),
            }
            #: shard_map specs for the pipeline path: pp on the stage dim,
            #: tp on heads/ffn — MUST mirror the logical axes above
            #: ("heads"/"mlp" → tp in mesh.DEFAULT_RULES)
            pipeline_specs = {
                "qkv_w": P("pp", None, None, "tp", None),
                "qkv_b": P("pp", None, "tp", None),
                "out_w": P("pp", "tp", None, None),
                "out_b": P("pp", None),
                "ln1_s": P("pp", None),
                "ln1_b": P("pp", None),
                "mlp_in_w": P("pp", None, "tp"),
                "mlp_in_b": P("pp", "tp"),
                "mlp_out_w": P("pp", "tp", None),
                "mlp_out_b": P("pp", None),
                "ln2_s": P("pp", None),
                "ln2_b": P("pp", None),
            }

            n_pp = mesh.shape.get("pp", 1) if mesh is not None else 1
            use_pipeline = n_pp > 1 and n_pp == config.pp_stages
            # tp/sp collectives are hand-written ONLY inside the pipeline's
            # shard_map; the sequential path is global-view (GSPMD)
            tp_world = (mesh.shape.get("tp", 1)
                        if (mesh is not None and use_pipeline) else 1)
            # pp×sp: the sequence stays sharded over sp inside the GPipe
            # schedule (pipeline_apply(seq_axis="sp")) and attention runs
            # the K/V ring directly — the sp axis is bound inside the
            # pipeline's shard_map, so ring_attention's ppermute/psum work
            # without their own shard_map wrapper
            sp_world = (mesh.shape.get("sp", 1)
                        if (mesh is not None and use_pipeline) else 1)

            def layer_norm(h, scale, bias):
                h32 = h.astype(jnp.float32)
                mu = h32.mean(axis=-1, keepdims=True)
                var = ((h32 - mu) ** 2).mean(axis=-1, keepdims=True)
                return ((h32 - mu) * jax.lax.rsqrt(var + 1e-6)
                        * scale + bias).astype(dtype)

            def block(lw, h, m):
                # local head count: nh/tp inside the pipeline shard_map
                hd_ = lw["qkv_w"].shape[-1]
                qkv = jnp.einsum(
                    "bsh,hknd->bsknd", h, lw["qkv_w"].astype(dtype)
                ) + lw["qkv_b"].astype(dtype)
                q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # (B,S,N,D)
                if sp_world > 1:
                    # pp×sp: h/m are LOCAL sequence blocks; K/V (and the
                    # key-padding mask) ppermute around the sp ring with a
                    # flash-style online softmax — same kernel as the
                    # layered model's long-context path.  Always the ring:
                    # ulysses' all_to_all does not lower inside the
                    # pipeline's nested scan (validated at construction)
                    from tensorflowonspark_tpu.parallel import (
                        ring_attention as ra,
                    )

                    o = ra.ring_attention(
                        q, k, v, axis_name="sp", kv_mask=m.astype(bool)
                    ).astype(dtype)
                else:
                    # same MXU policy as the layered Block: bf16 multiply
                    # with f32 accumulation, not an explicit f32-upcast
                    # matmul
                    sc = jnp.einsum(
                        "bqnd,bknd->bnqk", q, k,
                        preferred_element_type=jnp.float32,
                    ) * (1.0 / math.sqrt(hd_))
                    sc = jnp.where(m[:, None, None, :], sc, -1e30)
                    p = jax.nn.softmax(sc, axis=-1)
                    o = jnp.einsum("bnqk,bknd->bqnd", p.astype(dtype), v,
                                   preferred_element_type=jnp.float32
                                   ).astype(dtype)
                # row-sharded output projection: each tp rank contributes
                # its heads' partial sum; bias added AFTER the reduce
                o = jnp.einsum("bqnd,ndh->bqh", o, lw["out_w"].astype(dtype))
                if tp_world > 1:
                    o = jax.lax.psum(o, "tp")
                o = o + lw["out_b"].astype(dtype)
                h = layer_norm(h + o, lw["ln1_s"], lw["ln1_b"])
                y = nn.gelu(h @ lw["mlp_in_w"].astype(dtype)
                            + lw["mlp_in_b"].astype(dtype))
                y = y @ lw["mlp_out_w"].astype(dtype)
                if tp_world > 1:
                    y = jax.lax.psum(y, "tp")
                y = y + lw["mlp_out_b"].astype(dtype)
                return layer_norm(h + y, lw["ln2_s"], lw["ln2_b"])

            # per-layer rematerialization in BOTH execution paths (finer
            # than checkpointing a whole pipeline stage)
            blk = jax.checkpoint(block) if config.remat else block

            def stage_fn(sp, h, m):
                def body(carry, lw):
                    return blk(lw, carry, m), None

                h, _ = jax.lax.scan(body, h, sp)
                return h

            if use_pipeline:
                staged = jax.tree_util.tree_map(
                    lambda l: l.reshape((n_pp, L // n_pp) + l.shape[1:]), w
                )
                staged_specs = {
                    k: P("pp", None, *s[1:]) for k, s in pipeline_specs.items()
                }
                return pipeline_apply(
                    stage_fn, staged, x, mesh=mesh,
                    n_microbatches=config.pp_microbatches, aux=mask,
                    param_specs=staged_specs, seq_axis="sp",
                )
            return stage_fn(w, x, mask)

    class Bert(nn.Module):
        @nn.compact
        def __call__(self, input_ids, token_type_ids, attention_mask,
                     with_aux: bool = False):
            x = Embeddings(name="embeddings")(input_ids, token_type_ids)
            mask = attention_mask.astype(bool)
            aux_total = jnp.zeros((), jnp.float32)
            if config.pp_stages > 1:
                x = StackedEncoder(name="encoder")(x, mask)
            else:
                block_cls = nn.remat(Block) if config.remat else Block
                for i in range(config.layers):
                    is_moe = (config.moe_experts > 0
                              and (i + 1) % config.moe_every == 0)
                    x, aux = block_cls(moe=is_moe, name=f"layer_{i}")(x, mask)
                    aux_total = aux_total + aux
            # SQuAD span head: start/end logits per position
            span = dense((2,), ("embed", "classes"), name="span")(x)
            logits = span.astype(jnp.float32)
            logits = jnp.where(mask[:, :, None], logits, -1e30)
            start, end = logits[..., 0], logits[..., 1]  # (B, S)
            if with_aux:  # MoE training: router load-balance loss rides out
                return start, end, aux_total
            return start, end

    if config.sp_impl not in ("ring", "ulysses"):
        raise ValueError(
            f"sp_impl must be 'ring' or 'ulysses', got {config.sp_impl!r}")
    if config.moe_experts > 0:
        if config.pp_stages > 1:
            raise ValueError(
                "MoE (moe_experts > 0) runs in the layered trunk; combine "
                "ep with dp/fsdp/tp/sp, not pp_stages")
        n_ep = mesh.shape.get("ep", 1) if mesh is not None else 1
        if n_ep > 1 and config.moe_experts % n_ep:
            raise ValueError(
                f"moe_experts ({config.moe_experts}) must be divisible by "
                f"the mesh's ep axis ({n_ep})")
    if (mesh is not None and mesh.shape.get("sp", 1) > 1
            and config.sp_impl == "ulysses"):
        if config.pp_stages > 1 and mesh.shape.get("pp", 1) > 1:
            raise ValueError(
                "sp_impl='ulysses' is unsupported inside the GPipe trunk: "
                "all_to_all does not lower inside the pipeline's nested "
                "scan (XLA verifier rejects the reshard) — pp×sp uses "
                "sp_impl='ring' (the long-context-preferred kernel)")
        if config.heads % mesh.shape["sp"]:
            raise ValueError(
                f"ulysses sequence parallelism needs heads "
                f"({config.heads}) divisible by sp={mesh.shape['sp']}; "
                "use sp_impl='ring' or adjust heads")
    if config.pp_stages > 1:
        if config.layers % config.pp_stages:
            raise ValueError(
                f"layers={config.layers} not divisible by "
                f"pp_stages={config.pp_stages}"
            )
        mesh_tp = mesh.shape.get("tp", 1) if mesh is not None else 1
        if mesh_tp > 1:
            # pp×tp: each tp rank takes heads/tp heads and mlp_dim/tp ffn
            # columns inside every pipeline stage (StackedEncoder psums)
            if config.heads % mesh_tp or config.mlp_dim % mesh_tp:
                raise ValueError(
                    f"pp×tp needs heads ({config.heads}) and mlp_dim "
                    f"({config.mlp_dim}) divisible by tp={mesh_tp}"
                )
        mesh_pp = mesh.shape.get("pp", 1) if mesh is not None else 1
        if mesh_pp > 1 and mesh_pp != config.pp_stages:
            raise ValueError(
                f"mesh has pp={mesh_pp} but config.pp_stages="
                f"{config.pp_stages}: the trunk would fall back to "
                "sequential execution and replicate over every pp rank — "
                "make them equal"
            )
    elif mesh is not None and mesh.shape.get("pp", 1) > 1:
        raise ValueError(
            "mesh has pp > 1 but config.pp_stages <= 1: the layered model "
            "would replicate over every pp rank; set "
            "Config(pp_stages=mesh pp) for the GPipe trunk"
        )
    return Bert()


def make_loss_fn(module, config: Config):
    import jax.numpy as jnp
    import optax

    def loss_fn(params, batch):
        if config.moe_experts > 0:
            start, end, aux = module.apply(
                {"params": params}, batch["input_ids"],
                batch["token_type_ids"], batch["attention_mask"], True,
            )
        else:
            start, end = module.apply(
                {"params": params}, batch["input_ids"],
                batch["token_type_ids"], batch["attention_mask"],
            )
            aux = 0.0
        l_s = optax.softmax_cross_entropy_with_integer_labels(
            start, batch["start_positions"]
        )
        l_e = optax.softmax_cross_entropy_with_integer_labels(
            end, batch["end_positions"]
        )
        return jnp.mean(l_s + l_e) / 2.0 + config.moe_aux_weight * aux

    return loss_fn


def make_forward_fn(module, config: Config):
    def forward(params, batch):
        return module.apply(
            {"params": params}, batch["input_ids"], batch["token_type_ids"],
            batch["attention_mask"],
        )

    return forward


def example_batch(config: Config, batch_size: int = 8, seed: int = 0,
                  seq_len: int | None = None):
    rng = np.random.RandomState(seed)
    s = seq_len or min(config.max_len, 384)
    return {
        "input_ids": rng.randint(0, config.vocab_size, (batch_size, s)).astype(
            np.int32
        ),
        "token_type_ids": np.zeros((batch_size, s), np.int32),
        "attention_mask": np.ones((batch_size, s), np.int32),
        "start_positions": rng.randint(0, s, (batch_size,)).astype(np.int32),
        "end_positions": rng.randint(0, s, (batch_size,)).astype(np.int32),
    }
