"""The skeleton the five decoders trained on packed rows share
(``models/packed_decoder.py``) and the one seam their kernels' rules read
(``models/kernels.py``), each case once a decoder:

- a module that initialises declares its variables and traces no forward
  pass: none of the model's mixers, no routed layer, no ``*_pallas`` module;
- ``kernels.backend`` alone turns every rule a model uses, and the pairs of
  counters its ``batch_counters`` writes with them;
- the surface the registry promises (``models/__init__.py``) and the frozen
  callers under ``benchmark/`` use is on each decoder's module.

(That the skeleton computes what the four copies computed is not held here:
parameters, first loss and first gradients were bit-equal with the parent
commit in one sandbox, ``CHANGES.md`` PR 46; a digest of floats made on one
host would make this file unsteady on another.)
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from tensorflowonspark_tpu import models as zoo
from tensorflowonspark_tpu.models import kernels, packed_decoder
from tensorflowonspark_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: model -> (the benchmark's configuration, the functions a forward pass
#: calls, the counter pairs its step writes and which of them a chip's step
#: at the published shapes runs on the kernels)
DECODERS = {
    "granite_hybrid": (
        "granite_4_0_h_micro", ("mamba_mixer", "attention"),
        {"ssm_scan": 1, "conv": 1, "attention": 0}),
    "mla_moe": (
        "glm_4_7_flash", ("latent_attention", "prediction_states"),
        {"attention": 1, "moe_grouped": 1}),
    "lfm2_moe": (
        "lfm2_8b_a1b", ("conv_mixer", "attention"),
        {"conv": 1, "attention": 0, "moe_grouped": 1}),
    "kimi_linear": (
        "kimi_linear_48b_a3b", ("kda_mixer", "attention"),
        {"kda_scan": 1, "conv": 1, "attention": 0, "moe_grouped": 1}),
    "mellum_moe": (
        "mellum2_12b_a2_5b", ("attention",),
        {"attention": 1, "moe_grouped": 1}),
}


def _published(name: str):
    import importlib

    cell = DECODERS[name][0]
    program = importlib.import_module(f"benchmark.configs.{cell}.program")
    with open(os.path.join(REPO, "benchmark", "configs", cell,
                           "config.json")) as f:
        return program.model_config(json.load(f))


@pytest.mark.parametrize("name", sorted(DECODERS))
def test_init_declares_the_variables_and_traces_no_forward(name,
                                                           monkeypatch):
    """``make_model(Config.tiny()).init`` under ``jax.eval_shape`` gives
    exactly ``leaf_shapes`` and ``collection_shapes`` and calls none of the
    model's mixers nor ``routed_experts``; ``apply`` on what it gave calls
    them (the spies would see a forward pass)."""
    lib = zoo.get_model(name)
    config = lib.Config.tiny()
    called = []

    def spy(module, attr):
        real = getattr(module, attr)
        monkeypatch.setattr(module, attr, lambda *a, **k: (
            called.append(attr), real(*a, **k))[1])

    for attr in DECODERS[name][1]:
        spy(lib, attr)
    spy(moe, "routed_experts")
    model = lib.make_model(config)
    batch = lib.example_batch(config, batch_size=2)
    inputs = (batch["tokens"], batch["segment_ids"])
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), *inputs))
    assert not called
    assert {k: v.shape for k, v in shapes["params"].items()} == \
        lib.leaf_shapes(config)
    assert all(v.dtype == np.float32 for v in shapes["params"].values())
    state = getattr(lib, "collection_shapes", lambda config: None)(config)
    if state is None:
        assert set(shapes) == {"params"}
    else:
        assert {k: (v.shape, v.dtype.name)
                for k, v in shapes[lib.COLLECTION].items()} == state
    logits = jax.eval_shape(lambda v: model.apply(v, *inputs), shapes)
    assert logits.shape == inputs[0].shape + (config.vocab_size,)
    assert called


@pytest.mark.parametrize("name", sorted(DECODERS))
def test_a_start_imports_no_kernel_module(name):
    """In a process of its own, with the backend said to be a TPU and the
    published shapes (where every rule says "kernels"), ``init`` of the
    benchmark's configuration imports no ``*_pallas`` module: what
    ``Trainer.__init__`` traces twice to make the parameters pays for no
    kernel."""
    code = f"""
import json, os, sys
sys.path.insert(0, {REPO!r})
import jax
from tensorflowonspark_tpu import models as zoo
from tensorflowonspark_tpu.models import kernels
from benchmark.configs.{DECODERS[name][0]} import program
kernels.backend = lambda: "tpu"
with open(os.path.join({REPO!r}, "benchmark", "configs",
                       {DECODERS[name][0]!r}, "config.json")) as f:
    config = program.model_config(json.load(f))
lib = zoo.get_model({name!r})
batch = lib.example_batch(config, batch_size=1, seq_len=config.seq_len)
shapes = jax.eval_shape(lambda: lib.make_model(config).init(
    jax.random.PRNGKey(0), batch["tokens"], batch["segment_ids"]))
assert len(shapes["params"]) == len(lib.leaf_shapes(config))
print(json.dumps(sorted(m for m in sys.modules if m.endswith("_pallas"))))
"""
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == []


@pytest.mark.parametrize("name", sorted(DECODERS))
def test_one_seam_turns_every_rule_and_counter_pair(name, monkeypatch):
    """``kernels.backend`` is the only name patched: on the CPU every pair
    of ``batch_counters`` says plain at the published shapes; said to be a
    TPU, each of the model's sites says what a chip's step runs (attention
    stays plain where a head does not fill whole lanes), and at
    ``Config.tiny()``'s shapes all stay plain."""
    lib = zoo.get_model(name)
    wanted = DECODERS[name][2]
    config = _published(name)
    batch = {"segment_ids": np.zeros((1, config.seq_len), np.int32)}
    tiny = lib.Config.tiny()
    small = {"segment_ids": np.zeros((1, tiny.seq_len), np.int32)}

    def pairs(counts):
        sites = {k[:-len("_fused_steps_total")] for k in counts
                 if k.endswith("_fused_steps_total")}
        assert sites == {k[:-len("_plain_steps_total")] for k in counts
                         if k.endswith("_plain_steps_total")}
        return {site: (counts[site + "_fused_steps_total"],
                       counts[site + "_plain_steps_total"])
                for site in sites}

    assert jax.default_backend() == "cpu"
    assert pairs(lib.batch_counters(batch, config)) == {
        site: (0, 1) for site in wanted}
    monkeypatch.setattr(kernels, "backend", lambda: "tpu")
    assert pairs(lib.batch_counters(batch, config)) == {
        site: (fused, 1 - fused) for site, fused in wanted.items()}
    assert pairs(lib.batch_counters(small, tiny)) == {
        site: (0, 1) for site in wanted}


def test_the_seam_is_one_function():
    """One function of the package wraps ``jax.default_backend()`` for the
    kernels' rules, no module keeps a private copy, and no module but the
    skeleton reads ``is_initializing`` for a decoder."""
    import re

    found = {"backend": [], "initializing": []}
    root = os.path.join(REPO, "tensorflowonspark_tpu")
    for folder, _, files in os.walk(root):
        for file in files:
            if not file.endswith(".py"):
                continue
            path = os.path.join(folder, file)
            with open(path) as f:
                text = f.read()
            rel = os.path.relpath(path, root)
            if re.search(r"def _?backend\(|\._backend\b|import _backend",
                         text):
                found["backend"].append(rel)
            if "initializing" in text:
                found["initializing"].append(rel)
    assert found["backend"] == [os.path.join("models", "kernels.py")]
    assert sorted(found["initializing"]) == [
        os.path.join("models", "packed_decoder.py"),
        os.path.join("models", "widedeep.py")]


@pytest.mark.parametrize("name", sorted(DECODERS))
def test_the_surface_the_registry_promises_is_on_the_module(name):
    """What ``models/__init__.py`` lists and what ``benchmark/configs/*/
    program.py`` and ``tests/benchmark_checks/`` use: there, callable, and
    the shared ones one body (the same function of ``packed_decoder`` under
    every model's name)."""
    lib = zoo.get_model(name)
    config = lib.Config.tiny()
    for attr in ("make_model", "make_optimizer", "make_loss_fn",
                 "make_forward_fn", "example_batch", "batch_counters",
                 "leaf_shapes", "parameter_count", "apply_tokens"):
        assert callable(getattr(lib, attr)), attr
    assert lib.ADAMW == {"b1": 0.9, "b2": 0.95, "eps": 1e-8,
                         "weight_decay": 0.1}
    assert lib.SEQUENCE_AXES == {}
    assert lib.parameter_count(config) == sum(
        int(np.prod(s)) for s in lib.leaf_shapes(config).values())
    batch = lib.example_batch(config, batch_size=3, seed=1)
    assert batch["tokens"].shape == batch["segment_ids"].shape
    assert batch["tokens"].shape[0] == 3
    assert lib.example_batch(config, 1, seq_len=24)["tokens"].shape == (1, 24)
    for attr in ("make_model", "make_optimizer", "make_loss_fn",
                 "make_forward_fn", "example_batch", "parameter_count"):
        assert getattr(lib, attr).__func__ is getattr(
            packed_decoder.Decoder, attr), attr
    stateful = hasattr(lib, "COLLECTION")
    loss_fn = lib.make_loss_fn(None, config)
    forward = lib.make_forward_fn(None, config)
    assert (getattr(loss_fn, "stateful", False)
            == getattr(forward, "stateful", False) == stateful)
    if stateful:
        assert lib.COLLECTION == "moe"
        assert lib.device_counters.__func__ is \
            packed_decoder.Decoder.device_counters
        assert set(lib.collection_shapes(config)) == {
            "bias", "counts", "busiest", "overflow"}
        assert lib.routing(config).held == config.experts_held
    else:
        assert not hasattr(lib, "device_counters")
