"""JVM-side inference shim: C-ABI sequence via ctypes (simulating the JNI
call order), the no-Python-driver C demo, and the JNI library's exported
symbols (VERDICT r2 task 4 / SURVEY §2.2 rows 1-2)."""

import os
import subprocess
import sys

import numpy as np
import pytest

from tensorflowonspark_tpu import ckpt
from tensorflowonspark_tpu import models as model_zoo
from tensorflowonspark_tpu.native import infer_native

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def export(tmp_path_factory):
    """A tiny mnist_mlp export + its python-side forward for reference."""
    import jax

    lib = model_zoo.get_model("mnist_mlp")
    config = lib.Config.tiny()
    module = lib.make_model(config)
    batch = lib.example_batch(config, batch_size=1)
    from flax.linen import meta

    variables = meta.unbox(module.init(jax.random.PRNGKey(0), batch["image"]))
    params = variables["params"]
    path = str(tmp_path_factory.mktemp("export") / "model")
    ckpt.save_pytree({"params": params}, path)
    forward = lib.make_forward_fn(module, config)
    dim = config.image_size * config.image_size
    return path, params, forward, dim


@pytest.mark.skipif(not infer_native.available(),
                    reason="native toolchain unavailable")
def test_ctypes_jni_call_sequence(export):
    path, params, forward, dim = export
    x = (np.arange(4 * dim, dtype=np.float32) % 97) * 0.01
    x = x.reshape(4, dim)

    sess = infer_native.Session(path, "mnist_mlp")
    try:
        out = sess.predict(x)  # load → set_input("") → run → shape → output
    finally:
        sess.close()
    expected = np.asarray(forward(params, {"image": x}))
    assert out.shape == expected.shape
    np.testing.assert_allclose(out, expected, rtol=1e-5, atol=1e-5)


@pytest.mark.skipif(not infer_native.available(),
                    reason="native toolchain unavailable")
def test_named_input_and_reuse(export):
    path, params, forward, dim = export
    sess = infer_native.Session(path, "mnist_mlp")
    try:
        for batch_size in (2, 8):  # handle reuse across batch sizes
            x = np.random.default_rng(batch_size).normal(
                size=(batch_size, dim)).astype(np.float32)
            sess.set_input("image", x)
            sess.run()
            out = sess.output()
            expected = np.asarray(forward(params, {"image": x}))
            np.testing.assert_allclose(out, expected, rtol=1e-5, atol=1e-5)
    finally:
        sess.close()


@pytest.mark.skipif(not infer_native.available(),
                    reason="native toolchain unavailable")
def test_unknown_input_name_surfaces_python_error(export):
    path, _, _, dim = export
    sess = infer_native.Session(path, "mnist_mlp")
    try:
        with pytest.raises(RuntimeError, match="unknown input"):
            sess.set_input("nonexistent", np.zeros((1, dim), np.float32))
    finally:
        sess.close()


def test_demo_runs_without_python_driver(export):
    """A plain C process (no Python driver) scores a batch end-to-end."""
    demo = infer_native.demo_binary()
    if demo is None:
        pytest.skip("demo driver did not build")
    path, params, forward, dim = export
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.setdefault("TFOS_NUM_CHIPS", "0")
    proc = subprocess.run(
        [demo, path, "mnist_mlp", "4", str(dim)],
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    assert line.startswith("OK "), line
    # reproduce the demo's deterministic input and check the output sum
    x = ((np.arange(4 * dim, dtype=np.float32) % 97) * 0.01).reshape(4, dim)
    expected = float(np.asarray(forward(params, {"image": x})).sum())
    got = float(line.split("sum=")[1].split()[0])
    assert abs(got - expected) < 1e-3 * max(1.0, abs(expected)), (got, expected)


#: bounded retries for the harness STARTUP flake: rc -6
#: (``recursive_init_error`` SIGABRT) with EMPTY stdout is a native
#: static-init race in the embedded interpreter before the harness prints
#: anything — pre-existing, unrelated to the code under test.  A fresh
#: process reliably clears it; anything that produced output (or any
#: other rc) is a REAL result and is never retried.  Originally 4 when
#: the rate measured ~3/5 (PR 6); re-measured ~0 at PR 13
#: (TIER1_TIMES.json notes), so 2 now bounds the worst case while the
#: per-retry logging below keeps any recurrence visible.
_HARNESS_STARTUP_RETRIES = 2


def _run_harness(export_dir, model_name, batch, dim, tmpdir):
    harness = infer_native.jni_harness()
    if harness is None:
        pytest.skip("JNI harness did not build")
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.setdefault("TFOS_NUM_CHIPS", "0")
    for attempt in range(1 + _HARNESS_STARTUP_RETRIES):
        proc = subprocess.run(
            [harness, export_dir, model_name, str(batch), str(dim),
             str(tmpdir)],
            capture_output=True, text=True, timeout=600, env=env)
        if proc.returncode == -6 and not proc.stdout.strip() \
                and attempt < _HARNESS_STARTUP_RETRIES:
            # logged loudly so the flake RATE stays visible in test output
            # even while the retry keeps it from failing the suite
            print(f"jni harness startup flake (rc -6, empty stdout): "
                  f"retry {attempt + 1}/{_HARNESS_STARTUP_RETRIES}",
                  file=sys.stderr, flush=True)
            continue
        return proc
    return proc


def test_jni_glue_executes_under_fake_jvm(export, tmp_path):
    """VERDICT r3 item 2: every Java_* export EXECUTED, not just linked.

    The harness (native/jni_harness.cc) instantiates a real
    JNINativeInterface_ function table over a fake object model and drives
    load / setInput / setInputInts / setInputLongs / run / outputShape /
    getOutput / close plus both TFRecordCodec bindings — success AND
    exception paths, with copy-back array semantics and a leak check on
    Get*/Release* pairing."""
    path, params, forward, dim = export
    proc = _run_harness(path, "mnist_mlp", 4, dim, tmp_path)
    assert proc.returncode == 0, (proc.stdout + "\n" + proc.stderr)[-3000:]
    assert "JNI_HARNESS_PASS" in proc.stdout
    assert "JNI_CODEC_OK" in proc.stdout
    # numerics through the whole JNI marshalling stack match the python
    # forward (same deterministic input as the C demo)
    x = ((np.arange(4 * dim, dtype=np.float32) % 97) * 0.01).reshape(4, dim)
    expected = float(np.asarray(forward(params, {"image": x})).sum())
    got = float(proc.stdout.split("sum=")[1].split()[0])
    assert abs(got - expected) < 1e-3 * max(1.0, abs(expected))


def test_jni_glue_serves_self_describing_export(tmp_path):
    """The fake-JVM path × the SavedModel-parity export: a JVM scores a
    model with NO model name — inputs resolved from the serialized
    signature (VERDICT r3 items 1+2 combined)."""
    from tensorflowonspark_tpu import ckpt as _ckpt
    from tensorflowonspark_tpu import saved_model
    from tensorflowonspark_tpu.trainer import Trainer

    t = Trainer("mnist_mlp")
    d = str(tmp_path / "export")
    t.export(d)
    dim = t.config.image_size * t.config.image_size
    proc = _run_harness(d, "", 4, dim, tmp_path)
    assert proc.returncode == 0, (proc.stdout + "\n" + proc.stderr)[-3000:]
    assert "JNI_HARNESS_PASS" in proc.stdout
    fn, _sig = saved_model.load_forward(d)
    state = _ckpt.load_pytree(os.path.join(d, "model"))
    x = ((np.arange(4 * dim, dtype=np.float32) % 97) * 0.01).reshape(4, dim)
    expected = float(np.asarray(fn(state, {"image": x})).sum())
    got = float(proc.stdout.split("sum=")[1].split()[0])
    assert abs(got - expected) < 1e-3 * max(1.0, abs(expected))


@pytest.fixture(scope="module")
def two_output_export(tmp_path_factory):
    """A self-describing export whose forward returns TWO named outputs
    (plus a nested path) — the multi-output JVM serving fixture."""
    import jax.numpy as jnp

    from tensorflowonspark_tpu import compat

    rng = np.random.default_rng(7)
    state = {"params": {"w": rng.normal(size=(6, 3)).astype(np.float32)}}

    def forward(st, batch):
        z = batch["x"] @ st["params"]["w"]
        return {"embedding": z,
                "stats": {"norm": jnp.sum(z * z, axis=-1)}}

    d = str(tmp_path_factory.mktemp("multiout") / "export")
    example = {"x": np.zeros((2, 6), np.float32)}
    compat.export_saved_model(state, d, forward_fn=forward,
                              example_batch=example)
    return d, state, forward


@pytest.mark.skipif(not infer_native.available(),
                    reason="native toolchain unavailable")
def test_ctypes_named_multi_output(two_output_export):
    """VERDICT r4 item 3: every named output served through the C ABI —
    including the '/'-joined nested name — matching the python forward."""
    d, state, forward = two_output_export
    x = np.arange(4 * 6, dtype=np.float32).reshape(4, 6) * 0.1
    sess = infer_native.Session(d, "")
    try:
        sess.set_input("x", x)
        sess.run()
        names = sess.output_names()
        assert names == ["embedding", "stats/norm"]
        outs = sess.outputs()
        # "" resolves to the FIRST DECLARED output (dict insertion order,
        # not jax's sorted flatten order)
        first = sess.output("")
    finally:
        sess.close()
    import jax

    expected = jax.tree.map(np.asarray, forward(state, {"x": x}))
    np.testing.assert_allclose(outs["embedding"], expected["embedding"],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(outs["stats/norm"],
                               expected["stats"]["norm"],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(first, outs["embedding"])


def test_jni_glue_serves_named_outputs(two_output_export, tmp_path):
    """The fake-JVM harness enumerates outputCount/outputName and fetches
    BOTH named outputs; their sums match the python forward numerically
    (VERDICT r4 item 3 done-criterion)."""
    import jax

    d, state, forward = two_output_export
    proc = _run_harness(d, "", 4, 6, tmp_path)
    assert proc.returncode == 0, (proc.stdout + "\n" + proc.stderr)[-3000:]
    assert "JNI_HARNESS_PASS" in proc.stdout

    x = ((np.arange(4 * 6, dtype=np.float32) % 97) * 0.01).reshape(4, 6)
    expected = jax.tree.map(np.asarray, forward(state, {"x": x}))
    sums = {}
    for line in proc.stdout.splitlines():
        if line.startswith("JNI_NAMED "):
            fields = dict(kv.split("=", 1) for kv in line.split()[1:])
            sums[fields["name"]] = float(fields["sum"])
    assert set(sums) == {"embedding", "stats/norm"}
    for name, exp in (("embedding", expected["embedding"]),
                      ("stats/norm", expected["stats"]["norm"])):
        exp_sum = float(exp.sum())
        assert abs(sums[name] - exp_sum) < 1e-3 * max(1.0, abs(exp_sum)), (
            name, sums[name], exp_sum)


def test_jni_library_exports_expected_symbols():
    lib = infer_native.jni_library()
    if lib is None:
        pytest.skip("JNI wrapper did not build")
    syms = subprocess.run(["nm", "-D", lib], capture_output=True,
                          text=True).stdout
    for sym in (
        "Java_com_tensorflowonspark_tpu_TFosInference_load",
        "Java_com_tensorflowonspark_tpu_TFosInference_setInput",
        "Java_com_tensorflowonspark_tpu_TFosInference_setInputInts",
        "Java_com_tensorflowonspark_tpu_TFosInference_setInputLongs",
        "Java_com_tensorflowonspark_tpu_TFosInference_run",
        "Java_com_tensorflowonspark_tpu_TFosInference_outputShape",
        "Java_com_tensorflowonspark_tpu_TFosInference_getOutput",
        "Java_com_tensorflowonspark_tpu_TFosInference_close",
        "Java_com_tensorflowonspark_tpu_TFRecordCodec_writeRecords",
        "Java_com_tensorflowonspark_tpu_TFRecordCodec_indexRecords",
    ):
        assert sym in syms, f"missing JNI export {sym}"


@pytest.mark.skipif(not infer_native.available(),
                    reason="native toolchain unavailable")
def test_widedeep_collections_export_serves(tmp_path):
    """A collections-stateful model (wide&deep: embedding tables outside the
    param tree) must serve through the same C-ABI sequence — the criteo
    acceptance config's serving path without a Python driver."""
    import jax

    lib = model_zoo.get_model("wide_deep")
    config = lib.Config.tiny()
    module = lib.make_model(config)
    batch = lib.example_batch(config, batch_size=1)
    from flax.linen import meta

    variables = meta.unbox(
        module.init(jax.random.PRNGKey(0), batch["dense"], batch["cat"]))
    params = variables["params"]
    collections = {"embedding": variables["embedding"]}
    path = str(tmp_path / "model")
    ckpt.save_pytree({"params": params, "collections": collections}, path)

    full = lib.example_batch(config, batch_size=4, seed=1)
    sess = infer_native.Session(path, "wide_deep")
    try:
        sess.set_input("dense", full["dense"])
        sess.set_input("cat", full["cat"])
        sess.run()
        out = sess.output()
    finally:
        sess.close()
    forward = lib.make_forward_fn(module, config)
    expected = np.asarray(forward(params, collections,
                                  {"dense": full["dense"],
                                   "cat": full["cat"]}))
    assert out.shape == expected.shape
    np.testing.assert_allclose(out, expected, rtol=1e-4, atol=1e-5)
