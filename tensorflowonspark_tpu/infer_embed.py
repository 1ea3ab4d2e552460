"""In-process inference endpoint for the C-ABI / JNI shim.

Reference anchor: the reference ships a Scala inference API
(``src/main/scala/com/yahoo/tensorflowonspark/`` + ``pom.xml``,
``SURVEY.md §2.2`` row 1) so JVM Spark jobs can score models without a
Python driver.  The TPU rebuild's equivalent is ``libtfos_infer.so``
(``native/tfos_infer.cc``): a C shared library that embeds a CPython
interpreter and calls the functions below.  A JVM loads the library through
the JNI wrapper (``native/tfos_infer_jni.cc``) — no Python *process*
anywhere, just libpython linked into the JVM's address space, the same
pattern TF-Java used with libtensorflow.

The call protocol mirrors TF-Java's ``Session.Runner``: ``load`` →
``set_input``×N → ``run`` → ``get_output``.  All state lives in an integer
handle registry so the C side never holds Python object pointers.

Multi-output models serve every named output: after ``run``,
``output_count``/``output_name`` enumerate the flattened output names (the
signature's declared order first) and ``output_shape``/``get_output`` accept
a name (``""`` = the first declared output, the original single-output
convention).

Dtype contract: every output is served as **float32** (the C ABI's buffer
type, matching TF-Java's float fetch convention).  Integer outputs above
2^24 would lose exactness — emit such values as float from the model, or
serve through the Python ``TFModel`` path, which preserves dtypes.
"""

from __future__ import annotations

import itertools
import logging
import threading
from typing import Any

import numpy as np

logger = logging.getLogger(__name__)

_HANDLES: dict[int, dict[str, Any]] = {}
_NEXT = itertools.count(1)
_LOCK = threading.Lock()

#: dtype codes of the C ABI (tfos_infer.h)
_DTYPES = {0: np.float32, 1: np.int32, 2: np.int64}


def load(export_dir: str, model_name: str = "") -> int:
    """Load an export and its forward fn; returns a handle.

    Prefers the **self-describing** path: when the export carries a
    serialized forward + signature (``saved_model`` layout, the SavedModel
    parity artifact), the model is served from the artifact alone and
    ``model_name`` is ignored — a JVM can score models it has no Python
    code for.  Weights-only exports fall back to rebuilding the forward
    from the ``model_name`` zoo entry, as in rounds 1-3.
    """
    from tensorflowonspark_tpu import util

    util.ensure_jax_platform()
    import os

    import jax

    from tensorflowonspark_tpu import ckpt, compile_cache, saved_model

    # a JVM-embedded interpreter cold-starts like any other fleet member:
    # point the jit compiles below at the persistent cache
    compile_cache.ensure()

    path = export_dir
    model_sub = os.path.join(path, "model")
    if "://" not in path and os.path.isdir(model_sub):
        path = model_sub  # layout written by compat.export_saved_model
    state = ckpt.load_pytree(path)
    params = state.get("params", state) if isinstance(state, dict) else state
    collections = state.get("collections") if isinstance(state, dict) else None

    output_order: list[str] | None = None
    if saved_model.has_forward(export_dir):
        fn, sig = saved_model.load_forward(export_dir)
        params = state  # canonical serve(state, batch) takes the whole pytree
        input_names = [i["name"] for i in sig["inputs"]]
        output_order = [o["name"] for o in sig["outputs"]]
    else:
        from tensorflowonspark_tpu import models as model_zoo

        if not model_name:
            raise ValueError(
                f"export at {export_dir} is weights-only (no saved_forward/) "
                "— a model_name is required to rebuild the forward")
        lib = model_zoo.get_model(model_name)
        config = (lib.Config.tiny() if model_zoo._is_tiny(params, lib)
                  else lib.Config())
        module = lib.make_model(config)
        forward = lib.make_forward_fn(module, config)
        if getattr(forward, "stateful", False):
            cols = collections or {}
            fn = jax.jit(lambda p, b: forward(p, cols, b))
        else:
            fn = jax.jit(forward)

        # input names come from the zoo's example batch (labels stripped —
        # the shape-policy module's convention, shapes.LABEL_KEYS)
        from tensorflowonspark_tpu import shapes

        example = lib.example_batch(config, batch_size=1)
        input_names = [k for k in example if k not in shapes.LABEL_KEYS]

    with _LOCK:
        h = next(_NEXT)
        _HANDLES[h] = {
            "fn": fn,
            "params": params,
            "input_names": input_names,
            "output_order": output_order,
            "inputs": {},
            "outputs": None,  # ordered {name: float32 array} after run()
        }
    logger.info("infer_embed: loaded %s as handle %d (inputs %s)",
                export_dir, h, input_names)
    return h


def input_names(handle: int) -> str:
    """Comma-joined input tensor names (C side exposes for discovery)."""
    return ",".join(_HANDLES[handle]["input_names"])


def set_input(handle: int, name: str, data: bytes, shape: tuple,
              dtype_code: int) -> None:
    arr = np.frombuffer(data, _DTYPES[dtype_code]).reshape(shape)
    st = _HANDLES[handle]
    if name == "" and len(st["input_names"]) == 1:
        name = st["input_names"][0]  # single-input convenience
    if name not in st["input_names"]:
        raise KeyError(
            f"unknown input {name!r}; model inputs are {st['input_names']}")
    st["inputs"][name] = arr


def _flatten_named(out) -> dict[str, np.ndarray]:
    """Model output (array | tuple | nested dict) → ordered {name: float32}.

    Names follow the export signature's convention
    (``saved_model._leaf_name``): '/'-joined dict-key paths for nested
    dicts — so a model returning ``{"a": {"b": x}}`` serves output
    ``a/b`` — positional ``output_i`` for bare arrays, stringified indices
    for tuple members.  Mapping insertion order is preserved (JAX's own
    flatten sorts dict keys, which would lose the authored "first declared
    output" the C ABI's single-output convention depends on).
    """
    from collections.abc import Mapping as _Mapping

    named: dict[str, np.ndarray] = {}

    def rec(prefix: tuple, val) -> None:
        if isinstance(val, _Mapping):
            for k, v in val.items():
                rec(prefix + (str(k),), v)
        elif isinstance(val, (list, tuple)):
            for i, v in enumerate(val):
                rec(prefix + (str(i),), v)
        else:
            name = "/".join(prefix) if prefix else f"output_{len(named)}"
            named[name] = np.asarray(val, dtype=np.float32)

    rec((), out)
    return named


def run(handle: int) -> None:
    st = _HANDLES[handle]
    missing = [n for n in st["input_names"] if n not in st["inputs"]]
    if missing:
        raise ValueError(f"inputs not set before run: {missing}")
    batch = dict(st["inputs"])
    # bucketed batch shapes (serving data plane, reused): repeated JVM calls
    # with drifting batch sizes pad to the next power of two, so the jitted
    # forward compiles O(log n) shapes instead of one per distinct size.
    # Padding is evidence-gated per handle: slicing padded rows off is only
    # valid for a per-example forward (every output carries the batch
    # axis), so calls run at their true shape until per-example output
    # shapes have been observed at TWO DISTINCT batch sizes — a
    # batch-aggregating output has a FIXED size, which can coincide with at
    # most one batch size, so two distinct confirmations can only come from
    # outputs that genuinely track the batch axis.  Aggregating forwards
    # (pooled embedding, scalar metric) therefore keep exact-shape
    # execution and exact results.  Opt out entirely with
    # TFOS_INFER_BUCKETS=0.
    import os
    import time as _time

    from tensorflowonspark_tpu import serving, shapes

    bucketed = os.environ.get("TFOS_INFER_BUCKETS", "1").strip().lower() \
        not in ("0", "false")
    n_real = bucket = 0
    fresh = False
    if bucketed:
        # ladder policy from the ONE shape-policy module: implicit pow-2
        # buckets for callers with no configured geometry
        n_real = shapes.batch_rows(batch)
        bucket = shapes.pow2_bucket(n_real) if n_real > 0 else 0
        if bucket > n_real and (st.get("per_example") is not False
                                and len(st.get("per_example_sizes",
                                               ())) >= 2):
            batch = serving.pad_columns(batch, bucket)
        else:
            # not enough evidence yet (or evidence against): run at the
            # true shape — no pad copy is made; this call compiles at its
            # own size and its output shapes feed the evidence
            bucket = n_real
        fresh = serving.note_compile(("infer_embed", handle), batch)
    t0 = _time.perf_counter()
    out = st["fn"](st["params"], batch)
    named = _flatten_named(out)
    if fresh:
        # _flatten_named forced every output, so this wall carries the
        # first-call compile (or its persistent-cache load — the settle
        # in observe_compile_seconds tells them apart)
        serving.observe_compile_seconds(_time.perf_counter() - t0)
    if bucketed and n_real > 0:
        padded = bucket > n_real
        per_example = all(v.ndim >= 1 and v.shape[0] == bucket
                          for v in named.values())
        if padded and not per_example:
            # the evidence that enabled padding was wrong (the forward's
            # output arity changed under a new shape): rerun at the true
            # shape — correctness over the saved compile
            logger.warning(
                "handle %d: padded run produced non-per-example outputs; "
                "rerunning at the true batch size and disabling bucketing "
                "for this handle", handle)
            st["per_example"] = False
            true_batch = dict(st["inputs"])
            # the rerun is a genuine fresh compile at the true shape —
            # keep serving_compiles_total == jit compilation keys honest
            refresh = serving.note_compile(("infer_embed", handle),
                                           true_batch)
            t1 = _time.perf_counter()
            named = _flatten_named(st["fn"](st["params"], true_batch))
            if refresh:
                serving.observe_compile_seconds(_time.perf_counter() - t1)
        elif padded:
            # mask half of pad-and-mask: slice every output back to the
            # true row count (all carry the batch axis — just verified)
            named = {k: v[:n_real] for k, v in named.items()}
        elif per_example:
            st.setdefault("per_example_sizes", set()).add(n_real)
        else:
            st["per_example"] = False
    order = st.get("output_order")
    if order:
        # the signature's declared order wins; anything it doesn't name
        # (shouldn't happen, but never drop data) trails in flatten order
        ordered = {n: named[n] for n in order if n in named}
        ordered.update((n, v) for n, v in named.items() if n not in ordered)
        named = ordered
    st["outputs"] = named
    st["inputs"] = {}


def _resolve_output(handle: int, name: str = "") -> np.ndarray:
    st = _HANDLES[handle]
    outputs = st.get("outputs")
    if not outputs:
        raise ValueError("run() has not produced an output")
    if name == "":
        return next(iter(outputs.values()))  # first *declared* output
    if name not in outputs:
        raise KeyError(
            f"unknown output {name!r}; model outputs are {list(outputs)}")
    return outputs[name]


def output_count(handle: int) -> int:
    return len(_HANDLES[handle].get("outputs") or ())


def output_name(handle: int, index: int) -> str:
    outputs = _HANDLES[handle].get("outputs")
    if not outputs:
        raise ValueError("run() has not produced an output")
    names = list(outputs)
    if not 0 <= index < len(names):
        raise IndexError(f"output index {index} out of range "
                         f"({len(names)} outputs)")
    return names[index]


def output_shape(handle: int, name: str = "") -> tuple:
    return tuple(_resolve_output(handle, name).shape)


def get_output(handle: int, name: str = "") -> bytes:
    out = _resolve_output(handle, name)
    return np.ascontiguousarray(out, dtype=np.float32).tobytes()


def close(handle: int) -> None:
    from tensorflowonspark_tpu import serving

    serving.forget(("infer_embed", handle))
    with _LOCK:
        _HANDLES.pop(handle, None)
