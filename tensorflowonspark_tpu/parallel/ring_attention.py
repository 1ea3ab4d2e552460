"""Ring attention: sequence/context parallelism over the ``sp`` mesh axis.

Reference anchor: **absent from the reference** (``SURVEY.md §5``: "no ring
attention, no context parallel; sequence length bounded by single-device
memory").  The TPU rebuild makes long context first-class: the sequence axis
is sharded over ``sp``, each device holds a Q/K/V block, and K/V blocks
rotate around the ring via ``lax.ppermute`` (ICI neighbour exchanges) while
a flash-style online softmax accumulates — memory per device is
O(seq/sp · seq_block), never O(seq²), and the ppermute overlaps with the
block matmuls.

Two schemes (both differentiable — ``ppermute`` has a transpose rule, so
``jax.grad`` through the ring emits the reverse ring):

- :func:`ring_attention` — the ring proper (per-device fn under shard_map).
- :func:`ulysses_attention` — the all-to-all alternative: re-shard
  (seq/sp, heads) → (seq, heads/sp), run dense local attention, shard back.

Canonical layout: ``(batch, seq, heads, head_dim)``.
"""

from __future__ import annotations

import math
from typing import Any

NEG_INF = -1e30


def _block_attn(q, k, v, m, l, o, q_start, k_start, causal, scale,
                kv_mask=None):
    """One K/V block of flash-style attention with running (m, l, o).

    q: (B, Sq, H, D); k, v: (B, Sk, H, D); m, l: (B, H, Sq); o like q.
    ``q_start``/``k_start`` are the blocks' global sequence offsets (traced
    scalars — kept out of shapes so the loop stays compiled once).
    ``kv_mask``: optional (B, Sk) bool — False keys (padding) are excluded.
    """
    import jax.numpy as jnp

    # MXU policy: multiply in the inputs' dtype (bf16 for bf16 models),
    # accumulate f32 — an explicit f32-upcast matmul hits the chip's slow
    # multi-pass f32 path (see BENCH_NOTES.md round 4)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = q_start + jnp.arange(q.shape[1])
        k_pos = k_start + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :], s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    if causal:
        p = jnp.where(mask[None, None], p, 0.0)
    if kv_mask is not None:
        p = jnp.where(kv_mask[:, None, None, :], p, 0.0)
    correction = jnp.exp(m - m_new)
    l_new = l * correction + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    o_new = o * correction.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                   scale: float | None = None, kv_mask=None):
    """Per-device ring attention body; call under ``shard_map`` with the
    sequence axis sharded over ``axis_name``.

    Blocks rotate ``axis_size`` times; at step ``i`` this device holds the
    K/V block originally owned by rank ``(rank - i) mod n``.  ``kv_mask``
    (B, Sk local; False = padding key) rotates around the ring with its
    K/V block.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    n = lax.psum(1, axis_name)
    rank = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]

    m0 = jnp.full((b, h, sq), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, sq), dtype=jnp.float32)
    o0 = jnp.zeros(q.shape, dtype=jnp.float32)
    masked = kv_mask is not None  # trace-time: unmasked ring carries/permutes
    # no mask and skips the mask wheres entirely (packed fast path)

    def body(i, carry):
        m, l, o, kb, vb, maskb = carry
        src = (rank - i) % n
        # blocks stay in the model dtype end-to-end: the score matmul
        # accumulates f32 via preferred_element_type (_block_attn), with
        # no per-hop f32 upcast of the arriving block
        m, l, o = _block_attn(q, kb, vb,
                              m, l, o, rank * sq, src * sk, causal, scale,
                              kv_mask=maskb if masked else None)
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        if masked:
            maskb = lax.ppermute(maskb, axis_name, perm)
        return m, l, o, kb, vb, maskb

    maskb0 = kv_mask.astype(bool) if masked else jnp.zeros((b, 0), bool)
    m, l, o, _, _, _ = lax.fori_loop(0, n, body, (m0, l0, o0, k, v, maskb0))
    out = o / jnp.maximum(l, 1e-20).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                      scale: float | None = None, kv_mask=None):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style).

    Re-shards (seq/sp, H) → (seq, H/sp) with one ``all_to_all`` each way,
    runs dense local attention on the full sequence for a head subset.
    Requires ``heads % sp == 0``.  Better than the ring when sp is small and
    heads are plentiful; the ring wins at long seq / many chips.
    ``kv_mask`` (B, Sk local) is all-gathered to the full sequence.
    """
    import jax.numpy as jnp
    from jax import lax

    b, sq, h, d = q.shape
    n = lax.psum(1, axis_name)
    if h % n:
        raise ValueError(f"heads={h} not divisible by sp={n}")
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    def a2a_fwd(x):  # (B, Sq, H, D) -> (B, Sq*n, H/n, D)
        x = x.reshape(b, sq, n, h // n, d)
        x = lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=False)
        return x.reshape(b, sq * n, h // n, d)

    qg, kg, vg = a2a_fwd(q), a2a_fwd(k), a2a_fwd(v)
    s = jnp.einsum("bqhd,bkhd->bhqk", qg, kg,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        pos = jnp.arange(sq * n)
        s = jnp.where((pos[:, None] >= pos[None, :])[None, None], s, NEG_INF)
    if kv_mask is not None:
        # (B, Sk) -> (B, S global), concatenated in rank order — the same
        # order a2a_fwd reconstructs the sequence in
        mask_g = lax.all_gather(kv_mask.astype(bool), axis_name, axis=1,
                                tiled=True)
        s = jnp.where(mask_g[:, None, None, :], s, NEG_INF)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    p = p / p.sum(axis=-1, keepdims=True)
    og = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vg.dtype), vg,
                    preferred_element_type=jnp.float32)
    if kv_mask is not None:
        # query rows with NO visible key (all-padding, or causal window
        # fully padded) output 0, matching ring_attention (l = 0 there);
        # visibility comes from s so causal ∧ kv_mask compose correctly
        visible = (s > NEG_INF / 2).any(axis=-1)  # (B, H, Q)
        og = jnp.where(visible.transpose(0, 2, 1)[..., None], og, 0.0)

    # reverse: split seq chunks back to their devices, gather head groups
    og = og.reshape(b, n, sq, h // n, d)
    o = lax.all_to_all(og, axis_name, split_axis=1, concat_axis=2, tiled=False)
    o = o.reshape(b, sq, h, d)
    return o.astype(q.dtype)


def make_sharded_attention(mesh, causal: bool = False, impl: str = "ring"):
    """Wrap :func:`ring_attention` in ``shard_map`` over the full mesh.

    Inputs/outputs are global ``(batch, seq, heads, head_dim)`` arrays with
    batch over (dp, fsdp, ep) — matching ``mesh.batch_spec``, so an MoE
    model's sp attention doesn't all_gather the batch over ep and compute
    each attention layer redundantly per ep group — and seq over sp.
    Usable directly inside a jitted model: shard_map composes with jit and
    with grad.
    """
    from jax.sharding import PartitionSpec as P

    spec = P(("dp", "fsdp", "ep"), "sp", None, None)
    mask_spec = P(("dp", "fsdp", "ep"), "sp")
    fn = ring_attention if impl == "ring" else ulysses_attention

    def attn_plain(q, k, v):
        return fn(q, k, v, axis_name="sp", causal=causal)

    def attn_masked(q, k, v, kv_mask):
        return fn(q, k, v, axis_name="sp", causal=causal, kv_mask=kv_mask)

    mapped_plain = _shard_map(attn_plain, mesh,
                              in_specs=(spec, spec, spec), out_specs=spec)
    mapped_masked = _shard_map(attn_masked, mesh,
                               in_specs=(spec, spec, spec, mask_spec),
                               out_specs=spec)

    def attn(q, k, v, kv_mask=None):
        if kv_mask is None:  # packed/unmasked: no mask ppermute, no wheres
            return mapped_plain(q, k, v)
        return mapped_masked(q, k, v, kv_mask.astype(bool))

    return attn


def _shard_map(f, mesh, *, in_specs, out_specs):
    """``shard_map`` with replication checking off — now a thin alias of
    :func:`mesh.shard_map_unchecked` (shared with the bucketed gradient
    collectives and the ICI roofline probe); kept for existing callers."""
    from tensorflowonspark_tpu.parallel.mesh import shard_map_unchecked

    return shard_map_unchecked(f, mesh, in_specs=in_specs, out_specs=out_specs)


def local_attention(q, k, v, causal: bool = False, scale: float | None = None,
                    kv_mask=None):
    """Dense single-device attention with the same signature/layout —
    the sp=1 fallback, and the numerical baseline for ring tests."""
    import jax.numpy as jnp

    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :].astype(bool), s, NEG_INF)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    p = p / p.sum(axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    if kv_mask is not None:
        # query rows with NO visible key output 0, matching ring_attention
        # (causal ∧ kv_mask compose via s; see ulysses_attention)
        visible = (s > NEG_INF / 2).any(axis=-1)  # (B, H, Q)
        o = jnp.where(visible.transpose(0, 2, 1)[..., None], o, 0.0)
    return o.astype(q.dtype)
