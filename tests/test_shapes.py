"""The ONE shape-policy module (``tensorflowonspark_tpu/shapes.py``) and
the persistent compile cache it makes exact (``compile_cache.py``).

Covers the unification's load-bearing claims:

- signature canon: one fingerprint convention, stable ACROSS processes
  (the fleet-cache prerequisite), distinguishing shape/dtype/structure;
- ladder equivalence: the three legacy call sites (trainer warm-shape
  key, serving buckets, infer_embed pow-2) are literally the policy
  module's functions, not copies;
- enumeration == runtime: ``shapes.enumerate_signatures`` predicts
  exactly the jit keys the data plane requests, asserted via the compile
  counters — post-warmup transform/request/step adds ZERO new signatures;
- the compile cache's note_compile disk dimension (a disk hit is neither
  an in-process hit nor a true miss) and topology fencing.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tensorflowonspark_tpu import compile_cache, serving, shapes


# ---------------------------------------------------------------------------
# Signature canon
# ---------------------------------------------------------------------------


def test_signature_distinguishes_shape_dtype_and_structure():
    base = {"x": np.zeros((4, 3), np.float32)}
    assert shapes.signature(base) == shapes.signature(
        {"x": np.ones((4, 3), np.float32)})  # values don't matter
    assert shapes.signature(base) != shapes.signature(
        {"x": np.zeros((4, 3), np.float64)})  # dtype matters
    assert shapes.signature(base) != shapes.signature(
        {"x": np.zeros((3, 4), np.float32)})  # shape matters
    assert shapes.signature(base) != shapes.signature(
        {"y": np.zeros((4, 3), np.float32)})  # names matter
    # non-dict pytrees key by their whole structure (the ADVICE r5
    # regression: list vs reshaped list must not collapse to one key)
    assert shapes.signature([np.zeros((4,))]) != shapes.signature(
        [np.zeros((2, 2))])
    assert shapes.signature((np.zeros(2), np.zeros(2))) != \
        shapes.signature([np.zeros(2), np.zeros(2)])


def test_signature_signs_specs_like_arrays():
    """ShapeDtypeStruct leaves sign identically to materialized arrays —
    what lets enumeration run without allocating batches."""
    import jax

    arr = {"x": np.zeros((8, 3), np.float32), "m": np.zeros(8, np.int32)}
    spec = {"x": jax.ShapeDtypeStruct((8, 3), np.float32),
            "m": jax.ShapeDtypeStruct((8,), np.int32)}
    assert shapes.signature(arr) == shapes.signature(spec)


def test_signature_stable_across_processes():
    """The signature is plain data derived deterministically from the
    batch: a second interpreter computes the identical value — the
    property warmup-in-one-process / serve-in-another (and the
    shared-fs compile cache) depend on."""
    prog = (
        "import json, numpy as np\n"
        "from tensorflowonspark_tpu import shapes\n"
        "b = {'features': np.zeros((16, 4), np.float32),\n"
        "     'ids': np.zeros((16,), np.int64)}\n"
        "print(json.dumps(shapes.signature(b)))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=120,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-1000:]
    theirs = json.loads(out.stdout.strip().splitlines()[-1])
    ours = json.loads(json.dumps(shapes.signature(
        {"features": np.zeros((16, 4), np.float32),
         "ids": np.zeros((16,), np.int64)})))
    assert theirs == ours


def test_shape_policy_and_export_format_stay_below_the_cluster_stack(
        tmp_path):
    """``shapes``, ``saved_model`` and ``infer_embed`` — imported, then
    asked for the geometry loaded params imply and for an export's
    description — pull in neither ``pipeline`` nor ``TFCluster``."""
    prog = (
        "import sys\n"
        "import numpy as np\n"
        "from tensorflowonspark_tpu import util\n"
        "util.ensure_jax_platform()\n"
        "from tensorflowonspark_tpu import ckpt, infer_embed, saved_model, "
        "shapes\n"
        "specs = shapes.policy_specs('mnist_mlp', {'w': np.zeros(3)})\n"
        "assert specs, specs\n"
        f"ckpt.save_pytree({{'params': {{'w': np.zeros((3, 2))}}}}, "
        f"{str(tmp_path / 'exp')!r})\n"
        f"meta = saved_model.get_meta_graph_def({str(tmp_path / 'exp')!r})\n"
        "assert list(meta) == ['params/w'], meta\n"
        "high = [m for m in ('pipeline', 'TFCluster')\n"
        "        if 'tensorflowonspark_tpu.' + m in sys.modules]\n"
        "assert not high, high\n")
    out = subprocess.run([sys.executable, "-c", prog],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=120,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]


# ---------------------------------------------------------------------------
# Ladder equivalence with the three legacy call sites
# ---------------------------------------------------------------------------


def test_serving_ladder_is_the_policy_module():
    """serving's historical names ARE the policy functions (aliases, not
    copies) — the 'exactly one module' acceptance criterion."""
    assert serving.resolve_buckets is shapes.resolve_buckets
    assert serving.choose_bucket is shapes.choose_bucket
    assert serving.pow2_bucket is shapes.pow2_bucket
    assert serving.batch_rows is shapes.batch_rows
    assert serving.input_specs is shapes.input_specs
    assert serving.zero_batch is shapes.zero_batch


def test_trainer_warm_shape_key_is_policy_signature():
    """The watchdog key IS the policy signature — in its portable=False
    (treedef-object, type-exact) form, since it never crosses a process."""
    from tensorflowonspark_tpu.trainer import Trainer

    for batch in ({"x": np.zeros((4, 2), np.float32)},
                  [np.zeros((3,), np.int32)],
                  np.zeros((2, 2))):
        sig = Trainer._batch_signature(batch)
        assert sig == shapes.signature(batch, portable=False)
        # same leaf fingerprints as the portable form; only the
        # structure key differs (object vs string)
        assert sig[1] == shapes.signature(batch)[1]


def test_pow2_ladder_policy():
    assert [shapes.pow2_bucket(n) for n in (1, 2, 3, 5, 8, 9, 100)] == \
        [1, 2, 4, 8, 8, 16, 128]
    # mixed-arity batches report no paddable axis (zero-extending a
    # per-call side input would feed wrong values)
    assert shapes.batch_rows({"x": np.zeros((5, 2)),
                              "k": np.zeros((3,))}) == 0
    assert shapes.batch_rows({"x": np.zeros((5, 2)),
                              "y": np.zeros((5,))}) == 5


def test_resolve_buckets_matches_legacy_semantics():
    assert shapes.resolve_buckets(8) == (8,)
    assert shapes.resolve_buckets(8, [4, 2]) == (2, 4, 8)
    assert shapes.resolve_buckets(8, [16]) == (8,)  # oversize dropped
    assert shapes.resolve_buckets(8, [2, 4, 8, 8]) == (2, 4, 8)


def test_model_specs_strips_label_columns():
    specs = shapes.model_specs("mnist_mlp", tiny=True)
    assert "label" not in specs
    assert specs  # at least one genuine input
    for name, (tail, dtype) in specs.items():
        assert isinstance(tail, tuple)
        np.dtype(dtype)  # coercible


# ---------------------------------------------------------------------------
# Enumeration == runtime-requested shapes (via compile counters)
# ---------------------------------------------------------------------------


def _export_linear(tmp_path, in_dim=6, out_dim=2):
    from tensorflowonspark_tpu import compat

    rng = np.random.RandomState(0)
    w = rng.randn(in_dim, out_dim).astype(np.float32)
    export_dir = str(tmp_path / "export")
    compat.export_saved_model({"params": {"w": w}}, export_dir)
    return export_dir, w


def _linear_predict(params, batch):
    import jax.numpy as jnp

    return {"score": jnp.asarray(batch["x"]) @ params["w"]}


def test_warmup_enumerates_exactly_the_runtime_shapes(tmp_path):
    """The tentpole invariant: ``warmup`` warms exactly
    ``shapes.enumerate_signatures(specs, ladder)``, and a post-warmup
    transform over ragged partitions requests NO shape outside that set
    (compiles counter unchanged AND the per-model signature set equals
    the enumeration)."""
    import jax

    from tensorflowonspark_tpu import obs, pipeline
    from tensorflowonspark_tpu.pipeline import TFModel

    export_dir, w = _export_linear(tmp_path)
    model = (TFModel(predict_fn=jax.jit(_linear_predict))
             .setExportDir(export_dir).setBatchSize(8)
             .setInputMapping({"x": "x"}).setBucketSizes([4, 8]))
    compiles = obs.counter("serving_compiles_total")
    c0 = compiles.value
    example = {"x": np.zeros(6, np.float32)}
    warmed = model.warmup(example=example)
    assert warmed == [4, 8]
    assert compiles.value - c0 == 2

    specs = shapes.input_specs(example=example)
    enumerated = set(shapes.enumerate_signatures(specs, warmed))
    key = pipeline.model_cache_key(export_dir, None,
                                   model.predict_fn)
    assert serving._SEEN_SHAPES[key] == enumerated

    # ragged partitions through the data plane: every requested shape was
    # enumerated — zero new jit signatures after warmup
    rm = pipeline._RunModel(
        export_dir=export_dir, model_name=None,
        predict_fn=model.predict_fn, batch_size=8,
        input_mapping={"x": "x"}, output_mapping={"score": "score"},
        columns=["x"], backend="sparkapi", bucket_sizes=[4, 8])
    rows = [{"x": r} for r in
            np.random.RandomState(1).randn(11, 6).astype(np.float32)]
    out = list(rm(iter(rows)))
    assert len(out) == 11
    assert compiles.value - c0 == 2
    assert serving._SEEN_SHAPES[key] == enumerated


def test_post_warm_trainer_step_adds_zero_new_signatures():
    """The trainer half of the zero-new-signatures criterion: repeated
    steps at one batch geometry produce ONE warm-shape key (the watchdog
    arms from step 2 on), and the key is the policy signature."""
    from tensorflowonspark_tpu import trainer as trainer_lib

    t = trainer_lib.Trainer("mnist_mlp", step_timeout_s=600.0)
    lib = t.module_lib
    batch = lib.example_batch(t.config, batch_size=8)
    t.step(batch)
    warm1 = set(t._watchdog_warm_shapes)
    assert len(warm1) == 1
    assert next(iter(warm1)) == shapes.signature(batch, portable=False)
    t.step(batch)
    t.step(batch)
    assert set(t._watchdog_warm_shapes) == warm1  # zero new signatures


def test_warmup_policy_fallback_for_weights_only_zoo_export(tmp_path):
    """TFModel.warmup with neither example= nor a self-describing export
    falls back to shapes.model_specs when a model_name is set (the
    satellite: the zoo's example batch IS the input-shape policy), and
    the no-source error names shapes.py as the fix."""
    from tensorflowonspark_tpu import compat, models as model_zoo, obs
    from tensorflowonspark_tpu.pipeline import TFModel

    lib = model_zoo.get_model("mnist_mlp")
    config = lib.Config.tiny()
    import jax

    from tensorflowonspark_tpu.parallel.train import unbox

    module = lib.make_model(config)
    example = lib.example_batch(config, batch_size=1)
    params = unbox(module.init(jax.random.PRNGKey(0),
                               *(v for k, v in example.items()
                                 if k not in shapes.LABEL_KEYS)))["params"]
    export_dir = str(tmp_path / "zoo_export")
    compat.export_saved_model({"params": params}, export_dir)

    model = (TFModel().setExportDir(export_dir).setBatchSize(4)
             .setModelName("mnist_mlp"))
    compiles = obs.counter("serving_compiles_total")
    c0 = compiles.value
    warmed = model.warmup()  # no example, no signature: policy-derived
    assert warmed == [4]
    assert compiles.value - c0 == 1

    # and with NO source at all, the error names the policy module
    model2 = (TFModel(predict_fn=jax.jit(_linear_predict))
              .setExportDir(_export_linear(tmp_path / "plain")[0])
              .setBatchSize(4))
    with pytest.raises(ValueError, match="shapes.py"):
        model2.warmup()


# ---------------------------------------------------------------------------
# Compile cache: note_compile's disk dimension + topology fencing
# ---------------------------------------------------------------------------


@pytest.fixture()
def cache_dir_env(tmp_path, monkeypatch):
    """Cache on, placed the way an operator places it: through
    ``JAX_COMPILATION_CACHE_DIR``.  jax reads that variable at import, so
    the fixture hands the already-imported jax the same value."""
    import jax

    d = str(tmp_path / "cc")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    monkeypatch.delenv("TFOS_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("TFOS_COMPILE_CACHE_DIR", raising=False)
    compile_cache.disable()
    jax.config.update("jax_compilation_cache_dir", d)
    yield d
    compile_cache.disable()
    jax.config.update("jax_compilation_cache_dir", None)


def test_compile_cache_disabled_is_total_noop(monkeypatch):
    import jax

    monkeypatch.setenv("TFOS_COMPILE_CACHE", "0")
    compile_cache.disable()
    assert compile_cache.ensure() is None
    assert not compile_cache.active()
    assert jax.config.jax_compilation_cache_dir is None
    st = compile_cache.stats()
    assert st["enabled"] is False and st["namespace"] is None


def test_compile_cache_opt_out_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("TFOS_COMPILE_CACHE_DIR", str(tmp_path / "fleet"))
    monkeypatch.setenv("TFOS_COMPILE_CACHE", "0")
    compile_cache.disable()
    assert compile_cache.ensure() is None
    assert not compile_cache.active()
    compile_cache.disable()


def test_compile_cache_local_dir_and_writes(cache_dir_env):
    """ensure() uses the directory jax was given — no sub-namespace: jax's
    own key fences backend, device kind and version — and a first compile
    writes an entry the disk-writes counter sees."""
    import jax
    import jax.numpy as jnp

    ns = compile_cache.ensure()
    assert ns == cache_dir_env
    assert jax.config.jax_compilation_cache_dir == cache_dir_env
    assert compile_cache.active()
    assert compile_cache.stats()["dir"] == cache_dir_env

    writes0 = compile_cache.stats()["disk_writes"]
    salt = np.float32(np.random.RandomState(7).randn())  # unique jaxpr

    @jax.jit
    def fn(x):
        return jnp.tanh(x * salt) + 1.2345

    np.asarray(fn(np.zeros((3, 3), np.float32)))
    entries = [n for n in os.listdir(ns) if n.endswith("-cache")]
    assert entries, "first compile wrote no persistent-cache entry"
    assert compile_cache.stats()["disk_writes"] > writes0


def test_note_compile_disk_hit_is_neither_hit_nor_miss(cache_dir_env):
    """The satellite contract at the old serving.py:279 pointer: a
    first-call forward served from disk increments
    serving_compile_cache_disk_hits_total (via the cache layer) and NOT
    serving_compile_cache_misses_total — settled thread-exactly."""
    from tensorflowonspark_tpu import obs

    assert compile_cache.ensure() is not None
    misses = obs.counter("serving_compile_cache_misses_total")
    hits = obs.counter("serving_compile_cache_hits_total")
    disk = obs.counter("serving_compile_cache_disk_hits_total")
    m0, h0, d0 = misses.value, hits.value, disk.value

    key = ("disk_dim_test", id(object()))
    b = {"x": np.zeros((4, 2), np.float32)}
    assert serving.note_compile(key, b) is True
    # the forward "runs" and its compile is served from disk: the cache
    # layer's event fires on this thread
    compile_cache._on_event(compile_cache._EV_HIT)
    serving.observe_compile_seconds(0.5)
    assert disk.value - d0 == 1
    assert misses.value - m0 == 0  # a disk hit is not a true miss
    assert hits.value - h0 == 0    # ...and not an in-process hit

    # a second fresh signature that does NOT disk-hit settles as a miss
    assert serving.note_compile(key, {"x": np.zeros((8, 2),
                                                    np.float32)}) is True
    serving.observe_compile_seconds(0.5)
    assert misses.value - m0 == 1
    # and a repeat is an in-process hit, as ever
    assert serving.note_compile(key, dict(b)) is False
    assert hits.value - h0 == 1


def test_second_process_cold_start_hits_disk(cache_dir_env):
    """Two fresh interpreters, one cache dir: the first writes, the
    second loads — the fleet-cache mechanism end to end (tiny jit; the
    full tenant-path A/B lives in ``bench.py --compile-cache``)."""
    prog = (
        "import json, os\n"
        "import numpy as np\n"
        "from tensorflowonspark_tpu import compile_cache\n"
        "compile_cache.ensure()\n"
        "import jax, jax.numpy as jnp\n"
        "f = jax.jit(lambda x: jnp.tanh(x @ x) * 3.25)\n"
        "np.asarray(f(np.ones((17, 17), np.float32)))\n"
        "print(json.dumps(compile_cache.stats()))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache_dir_env)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def run():
        out = subprocess.run([sys.executable, "-c", prog], env=env,
                             capture_output=True, text=True, timeout=180,
                             cwd=repo)
        assert out.returncode == 0, out.stderr[-1000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    first = run()
    assert first["disk_writes"] >= 1
    assert first["disk_hits"] == 0
    second = run()
    assert second["disk_hits"] >= 1


def test_healthz_surfaces_compile_cache_state():
    """/healthz (OnlineServer.stats) carries the compile-cache block —
    dir + counters + warm_ratio — so a router can see a cold replica."""
    from tensorflowonspark_tpu import online

    srv = online.OnlineServer()
    try:
        doc = srv.stats()
        cc = doc["compile_cache"]
        for k in ("enabled", "dir", "namespace", "disk_hits",
                  "disk_writes", "compiles_total", "in_process_hits",
                  "true_misses", "warm_ratio"):
            assert k in cc, k
        json.dumps(doc)  # the whole body stays JSON-able
    finally:
        srv.stop()


def test_topology_key_is_deterministic_and_fences():
    k1, k2 = compile_cache.topology_key(), compile_cache.topology_key()
    assert k1 == k2
    assert "/" not in k1 and k1 == k1.strip()
    import jax

    assert jax.default_backend() in k1
    assert jax.__version__.replace("+", "-") in k1 or jax.__version__ in k1
