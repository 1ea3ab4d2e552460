"""The ONE compile-triggering shape-policy module.

Every XLA compile in this framework is keyed by a batch's shape signature
— and until this module, the policy that decides WHICH shapes a process
requests lived in three places that could drift independently:

- the trainer's watchdog warm-shape key (``trainer.Trainer._batch_signature``),
- the serving bucket ladder (``serving.resolve_buckets`` / ``choose_bucket``),
- the JNI shim's implicit pow-2 ladder (``infer_embed.run``).

Drift between them is not cosmetic: ``TFModel.warmup`` (and the online
tier's warm-on-load) promises to pre-compile *exactly* the shapes the
runtime will request, and the persistent compile cache
(:mod:`tensorflowonspark_tpu.compile_cache`) amortizes compiles across a
fleet only if every process derives the same shapes from the same config.
A warm loop that enumerates even one shape differently from the data plane
re-pays a full XLA compile on the first request — the fleet cold-start
cost this module exists to eliminate (ROADMAP item 4; the per-shape JIT
specialization cost is the TensorFlow paper's own cold-start story,
arXiv:1605.08695, and replica-fleet designs amortize it by making workers
identical, TF-Replicator arXiv:1902.00465).

Three policy surfaces, one home:

- **Shape signatures** (:func:`signature`): the canonical fingerprint of a
  batch's (structure, shape, dtype) tree — exactly what ``jax.jit`` keys
  its executable cache on.  Plain data (strings/ints only), so the same
  batch produces the same signature in every process — the property the
  fleet cache and the warmup-enumeration tests rely on.
- **Ladder resolution** (:func:`resolve_buckets` / :func:`choose_bucket` /
  :func:`pow2_bucket` / :func:`batch_rows`): which padded batch shapes a
  serving config compiles.
- **Per-model shape enumeration** (:func:`input_specs` / :func:`zero_batch`
  / :func:`enumerate_signatures` / :func:`model_specs`): given a model's
  row templates and a ladder, the complete, finite set of signatures the
  runtime will request — what warmup warms and what the persistent cache
  is seeded with.

``serving`` re-exports the ladder/spec helpers under their historical
names; new code should import them from here.
"""

from __future__ import annotations

import logging
from typing import Any, Mapping, Sequence

import numpy as np

logger = logging.getLogger(__name__)

#: zoo example-batch keys that are training targets, not model inputs —
#: stripped when deriving serving input specs from a model-zoo entry
#: (the convention ``infer_embed.load`` established for weights-only
#: exports)
LABEL_KEYS = frozenset({"label", "start_positions", "end_positions"})


# ---------------------------------------------------------------------------
# Shape signatures
# ---------------------------------------------------------------------------


def signature(batch: Any, *, portable: bool = True) -> tuple:
    """Canonical, hashable fingerprint of a batch's full (structure,
    shape, dtype) tree — what ``jax.jit`` keys its executable cache on,
    so for a jitted forward "new signature" == "fresh XLA compile".

    One signature convention for every consumer: the trainer's watchdog
    warm-shape key (a dtype-only change with identical shapes, or any
    reshape of a non-dict batch, must read as a DIFFERENT signature — an
    armed watchdog window across the recompile would read minutes of XLA
    as a wedge), the serving planes' compile accounting
    (``serving.note_compile``), and warmup enumeration
    (:func:`enumerate_signatures`).

    The default (``portable=True``) result is plain data — the treedef's
    string form plus ``(shape, dtype)`` per leaf in flatten order — so
    the same batch yields the same signature in every process (dict keys
    are sorted by the flatten, exactly as jit sees them).  Leaves only
    need ``shape`` / ``dtype`` attributes: real arrays and
    ``jax.ShapeDtypeStruct`` specs sign identically, which is what lets
    enumeration run without materializing batches.

    ``portable=False`` keys on the treedef OBJECT instead of its string
    — type-exact, the safety-critical choice for the trainer's
    *in-process* watchdog key: two registered pytree node classes with
    identical string forms (same-named dataclasses from different
    modules) must not alias, or an armed window would span their
    recompile and kill a healthy trainer.  Serving batches are plain
    dicts of arrays, where the string form is already exact, so the
    portable default stays correct for the cross-process uses (warmup
    enumeration, the fleet compile cache's accounting).
    """
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(batch)
    return (str(treedef) if portable else treedef, tuple(
        (tuple(int(d) for d in getattr(leaf, "shape", np.shape(leaf))),
         str(getattr(leaf, "dtype", type(leaf).__name__)))
        for leaf in leaves))


# ---------------------------------------------------------------------------
# Sharded-update shape policy
# ---------------------------------------------------------------------------


def update_shard_eligible(shape: Sequence[int], itemsize: int, world: int,
                          min_bytes: int) -> bool:
    """Can a parameter of this shape take the reduce-scatter weight-update
    path (``parallel/collectives.py``)?

    Shape policy, not mechanism — which is why it lives here: the sharded
    update stores a leaf's optimizer state as a dim-0 slice per replica
    (``P((data_axes...), None, ...)``), and its gradient arrives as the
    matching block of a flattened ``psum_scatter``.  The two coincide
    without any resharding hop exactly when the leading dimension divides
    the data-parallel world — row-major flat block *k* of a
    ``(d0, ...)``-shaped leaf IS rows ``[k·d0/N, (k+1)·d0/N)`` iff
    ``d0 % N == 0``.  Three conditions:

    - ``shape`` is non-scalar and ``shape[0] % world == 0`` (the
      block/slice coincidence above);
    - ``world >= 2`` (a single replica has nothing to scatter);
    - the leaf is at least ``min_bytes`` big — aligned with the ZeRO
      threshold (``train.zero_min_bytes``), so leaves too small to be
      worth sharding ride a replicated fast path instead of forcing a
      degenerate one-leaf scatter bucket.

    Every process evaluates this from static shapes only, so the whole
    fleet derives the identical bucket schedule — the same determinism
    contract as :func:`signature`.
    """
    if world < 2 or not shape:
        return False
    d0 = int(shape[0])
    if d0 <= 0 or d0 % world != 0:
        return False
    size = 1
    for d in shape:
        size *= int(d)
    return size * int(itemsize) >= int(min_bytes)


# ---------------------------------------------------------------------------
# Ladder resolution
# ---------------------------------------------------------------------------


def resolve_buckets(batch_size: int,
                    bucket_sizes: Sequence[int] | None = None
                    ) -> tuple[int, ...]:
    """The effective bucket set: sorted, deduplicated, positive.

    Default (``bucket_sizes`` unset/empty) is the single bucket
    ``(batch_size,)`` — every batch, ragged tails included, pads to the one
    compiled shape.  Extra buckets trade padding waste for compile count:
    ``[batch_size // 4, batch_size]`` wastes at most 75% on a tiny tail
    while compiling twice.  Two normalizations keep the set sane: buckets
    larger than ``batch_size`` are DROPPED (with a warning — chunking
    never produces a batch bigger than ``batch_size``, so an oversize
    bucket would only ever make :func:`choose_bucket` pad full batches up
    past their own size), and the terminal ``batch_size`` bucket is always
    included (a set whose largest bucket is smaller than ``batch_size``
    would compile every tail above it at its own shape — the per-tail
    compile explosion buckets exist to prevent).
    """
    if bucket_sizes:
        out = sorted({int(b) for b in bucket_sizes if int(b) > 0})
        kept = [b for b in out if b <= int(batch_size)]
        if len(kept) != len(out):
            logger.warning(
                "dropping bucket size(s) %s > batch_size %d: a batch never "
                "exceeds batch_size, so an oversize bucket would only pad "
                "full batches past their own size",
                [b for b in out if b > int(batch_size)], int(batch_size))
        if kept:
            if kept[-1] < int(batch_size):
                # the terminal bucket must cover batch_size-row chunks, or
                # every tail above it compiles at its own shape — the
                # per-tail compile explosion buckets exist to prevent
                kept.append(int(batch_size))
            return tuple(kept)
    return (int(batch_size),)


def choose_bucket(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket that fits ``n`` rows; ``n`` itself when none does
    (only reachable when the caller's chunk size exceeds every bucket —
    the batch then compiles at its own shape, exactly the legacy cost)."""
    for b in buckets:
        if b >= n:
            return int(b)
    return int(n)


def pow2_bucket(n: int) -> int:
    """Next power-of-two ≥ n — the implicit bucket ladder used by callers
    with no configured geometry (``infer_embed``'s JVM batches)."""
    b = 1
    while b < n:
        b <<= 1
    return b


def prefill_buckets(max_prompt_len: int, *, min_bucket: int = 8,
                    cap: int | None = None) -> tuple[int, ...]:
    """The generative-decode PREFILL ladder: power-of-two prompt-length
    buckets from ``min_bucket`` up to the one covering
    ``max_prompt_len``, optionally capped at ``cap`` (the model's
    positional capacity ``max_len`` — a bucket longer than the position
    table cannot be embedded).

    This is the decode tier's compile-triggering shape policy: every
    prompt pads to a ladder bucket, so prefill compiles once per BUCKET
    and the decode step (whose shapes are fixed by the slot/page
    geometry, not the sequence length) compiles exactly once — sequence
    growth never mints a new jit signature.  Pure arithmetic (no
    env, no device state), so every process derives the identical
    ladder from the same config — the fleet-compile-cache discipline.

    When the covering power of two exceeds ``cap``, the terminal bucket
    is ``max_prompt_len`` itself (one exact-fit compile instead of an
    un-embeddable shape).
    """
    max_prompt_len = int(max_prompt_len)
    if max_prompt_len < 1:
        raise ValueError(f"max_prompt_len must be >= 1, got {max_prompt_len}")
    terminal = pow2_bucket(max_prompt_len)
    if cap is not None and terminal > int(cap):
        if max_prompt_len > int(cap):
            raise ValueError(
                f"max_prompt_len {max_prompt_len} exceeds cap {cap}")
        terminal = max_prompt_len
    out: list[int] = []
    b = pow2_bucket(max(1, int(min_bucket)))
    while b < terminal and b < max_prompt_len:
        out.append(b)
        b <<= 1
    out.append(terminal)
    return tuple(out)


def prefill_chunks(max_prompt_len: int, page_size: int, *,
                   max_chunk: int | None = None) -> tuple[int, ...]:
    """The CHUNKED-prefill ladder: page-aligned chunk lengths the decode
    tier compiles its multi-sequence prefill step at.

    Chunked prefill splits every prompt into page-aligned chunks and
    packs chunks from several requests into one jitted call of fixed
    ``(chunks, chunk_len)`` geometry — ``chunk_len`` must come from this
    ladder, so prefill compiles once per RUNG and a long prompt advances
    at most ``max_chunk`` tokens per engine step (the TTFT bound: decode
    steps interleave between chunks, so a long prompt cannot monopolize
    the loop).  Page alignment is load-bearing twice over: a chunk
    boundary always lands on a page boundary (so a chunk never
    half-fills a page another chunk must append to mid-call), and the
    prefix-sharing registry maps whole pages, so shared prefixes compose
    with chunk boundaries without remapping.

    Rungs are power-of-two multiples of ``page_size`` (``ps, 2ps, 4ps,
    ...``) up to the terminal rung: the page-aligned cover of
    ``max_prompt_len``, capped at ``max_chunk`` rounded DOWN to a page
    multiple (never below one page).  Pure arithmetic — no env, no
    device state — so every process derives the identical ladder from
    the same config, same as :func:`prefill_buckets`.
    """
    max_prompt_len = int(max_prompt_len)
    page_size = int(page_size)
    if max_prompt_len < 1:
        raise ValueError(f"max_prompt_len must be >= 1, got {max_prompt_len}")
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    cover = -(-max_prompt_len // page_size) * page_size
    top = cover
    if max_chunk is not None:
        budget = max(page_size, (int(max_chunk) // page_size) * page_size)
        top = min(top, budget)
    out: list[int] = []
    rung = page_size
    while rung < top:
        out.append(rung)
        rung <<= 1
    out.append(top)
    return tuple(out)


def spec_ladder(spec_tokens: int) -> tuple[int, ...]:
    """The SPECULATION ladder: the draft lengths ``k`` the decode tier
    compiles its verify step at, ascending, ending at the configured
    ``spec_tokens``.

    The verify step scores ``k+1`` positions per slot in one fixed-shape
    call, so each rung is one jit signature of ``(max_seqs, k+1)``
    geometry.  The adaptive controller moves BETWEEN rungs (halving on a
    cold drafter, restoring on a hot one) and every rung is compiled at
    warmup — which is what lets the controller change ``k`` mid-flight
    without minting a signature (the zero-new-signatures invariant,
    same discipline as :func:`prefill_chunks`).  Rungs halve from the
    top: ``spec_tokens, spec_tokens // 2, ..., 1``.  Pure arithmetic —
    no env, no device state — so every process derives the identical
    ladder from the same config.
    """
    spec_tokens = int(spec_tokens)
    if spec_tokens < 1:
        raise ValueError(f"spec_tokens must be >= 1, got {spec_tokens}")
    out: list[int] = []
    rung = spec_tokens
    while rung > 1:
        out.append(rung)
        rung //= 2
    out.append(1)
    return tuple(reversed(out))


def batch_rows(batch: Mapping[str, Any]) -> int:
    """The batch's paddable row count: the leading dimension EVERY
    ``ndim >= 1`` input shares — that shared dimension is what makes it a
    batch axis.  0 when there is no leading axis anywhere or the leading
    dims disagree (e.g. a per-call side input of shape ``(k,)`` riding
    along with ``(n, d)`` features — zero-extending *that* would feed the
    model wrong values, not padding)."""
    dims = {int(np.shape(v)[0]) for v in batch.values()
            if np.asarray(v).ndim >= 1}
    if len(dims) != 1:
        return 0
    n = dims.pop()
    return n if n > 0 else 0


# ---------------------------------------------------------------------------
# Per-model shape enumeration
# ---------------------------------------------------------------------------


def input_specs(example: Mapping[str, Any] | None = None,
                signature: Mapping[str, Any] | None = None
                ) -> dict[str, tuple[tuple, Any]]:
    """Per-input row templates: ``{input_name: (row_shape, dtype)}``.

    The shape source for :func:`zero_batch` — what a warmup path needs to
    build a representative batch at any bucket size.  From ``example`` (a
    dict of input name → ONE example row, no batch axis) the template is
    the row's own shape/dtype; from a self-describing export's
    ``signature`` (``saved_model.read_signature``) it is each input
    entry's shape minus the leading batch dim.  Exactly one source must
    be given.  (The ``signature`` parameter is the export artifact's
    signature document — unrelated to :func:`signature` above, which it
    shadows locally.)
    """
    if (example is None) == (signature is None):
        raise ValueError("input_specs needs exactly one of example= / "
                         "signature=")
    specs: dict[str, tuple[tuple, Any]] = {}
    if example is not None:
        for name, row in example.items():
            a = np.asarray(row)
            specs[str(name)] = (tuple(a.shape), a.dtype)
        return specs
    for entry in signature.get("inputs", []):
        shape = entry.get("shape") or []
        if any(d is None for d in shape[1:]):
            raise ValueError(
                f"input {entry.get('name')!r} has a polymorphic non-batch "
                f"dim {shape}: warmup needs concrete row shapes — pass "
                "example= instead")
        tail = tuple(int(d) for d in shape[1:])
        specs[str(entry["name"])] = (tail, np.dtype(entry["dtype"]))
    if not specs:
        raise ValueError("signature carries no inputs")
    return specs


def model_specs(model_name: str, *, tiny: bool = False
                ) -> dict[str, tuple[tuple, Any]]:
    """Input specs derived from a model-zoo entry's own example batch —
    the policy fallback for weights-only exports served by
    ``model_name`` (no ``example=`` in hand, no self-describing
    signature on disk).  Training targets (:data:`LABEL_KEYS`) are
    stripped: they are loss inputs, not serving inputs.  ``tiny``
    selects the zoo's ``Config.tiny()`` geometry (the same choice
    ``models._is_tiny`` makes from loaded params)."""
    from tensorflowonspark_tpu import models as model_zoo

    lib = model_zoo.get_model(model_name)
    config = lib.Config.tiny() if tiny else lib.Config()
    example = lib.example_batch(config, batch_size=1)
    rows = {k: np.asarray(v)[0] for k, v in example.items()
            if k not in LABEL_KEYS}
    if not rows:
        raise ValueError(
            f"model {model_name!r}: example batch carries only label "
            f"columns {sorted(example)} — no serving inputs to derive")
    return input_specs(example=rows)


def policy_specs(model_name: str, params: Any
                 ) -> dict[str, tuple[tuple, Any]]:
    """:func:`model_specs` at the geometry the loaded ``params`` imply —
    THE zoo-fallback shape source, shared by ``TFModel.warmup`` and
    ``OnlineServer.add_tenant`` so the batch and online tiers can never
    drift on what a weights-only ``model_name`` export warms."""
    from tensorflowonspark_tpu import models as model_zoo

    lib = model_zoo.get_model(model_name)
    return model_specs(model_name, tiny=model_zoo._is_tiny(params, lib))


def zero_batch(specs: Mapping[str, tuple[tuple, Any]], rows: int) -> dict:
    """An all-zeros batch of ``rows`` rows shaped by :func:`input_specs` —
    the shape/dtype signature is what jit keys on, so a zero batch warms
    exactly the compile a real batch of the same geometry would pay."""
    return {name: np.zeros((int(rows), *tail), dtype)
            for name, (tail, dtype) in specs.items()}


def enumerate_signatures(specs: Mapping[str, tuple[tuple, Any]],
                         buckets: Sequence[int]) -> list[tuple]:
    """The complete set of shape signatures a bucketed runtime will
    request for one model: one :func:`signature` per ladder bucket.

    This is the warmup/enumeration contract made testable: with
    bucketing on, every data-plane batch pads to a ladder bucket, so the
    signatures the runtime hands ``serving.note_compile`` are exactly
    this list — a post-warmup transform/request adds ZERO new jit keys
    (asserted in ``tests/test_shapes.py`` via the compile counters).
    Enumeration signs ``jax.ShapeDtypeStruct`` specs instead of
    materializing arrays — :func:`signature` reads only shape/dtype, so
    the result is identical to signing :func:`zero_batch` output.
    """
    import jax

    out = []
    for b in buckets:
        batch = {name: jax.ShapeDtypeStruct((int(b), *tail), np.dtype(dt))
                 for name, (tail, dt) in specs.items()}
        out.append(signature(batch))
    return out
