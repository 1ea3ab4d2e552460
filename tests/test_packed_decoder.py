"""The skeleton the six decoders trained on packed rows share
(``models/packed_decoder.py``) and the one seam their kernels' rules read
(``models/kernels.py``), each case once a decoder:

- a module that initialises declares its variables and traces no forward
  pass: none of the model's mixers, no routed layer, no ``*_pallas`` module;
- ``kernels.backend`` alone turns every rule a model uses, and the pairs of
  counters its ``batch_counters`` writes with them;
- the surface the registry promises (``models/__init__.py``) and the frozen
  callers under ``benchmark/`` use is on each decoder's module;
- a layer's recomputation keeps what attention names
  (``packed_rows.ATTENTION_SAVED``): the forward blocks run once in a
  gradient, the gradient is the one the second run gave, and a layer with
  no attention lowers to the text it had;
- what ``packed_rows.swiglu`` names (``SWIGLU_SAVED``) and no policy lists
  lowers to nothing: the four expert decoders' steps are the text they were.

(That the skeleton computes what the four copies computed is not held here:
parameters, first loss and first gradients were bit-equal with the parent
commit in one sandbox, ``CHANGES.md`` PR 46; a digest of floats made on one
host would make this file unsteady on another.)
"""

import dataclasses
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
    "afmoe": (
        "trinity_mini", ("attention",),
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
            "bias", "counts", "busiest", "overflow", "tight",
            *getattr(lib, "GATE_OPEN", ())}     # and a model's gauges
        assert lib.routing(config).held == config.experts_held
    else:
        assert not hasattr(lib, "device_counters")


#: model -> what makes ``Config.tiny()`` one layer, of attention
ONE_ATTENTION_LAYER = {
    "granite_hybrid": dict(layer_types=("attention",)),
    "mla_moe": dict(num_hidden_layers=1, first_k_dense_replace=0,
                    num_nextn_predict_layers=0),
    "lfm2_moe": dict(layer_types=("full_attention",), num_dense_layers=1),
    "kimi_linear": dict(num_hidden_layers=1, kda_layers=(),
                        full_attn_layers=(1,)),
    "mellum_moe": dict(layer_types=("full_attention",), layers_run=(0,)),
    "afmoe": dict(layer_types=("full_attention",), layers_run=(0,),
                  num_dense_layers=1),
}
#: ... and what makes that layer's heads fill the kernels' tiles (the three
#: whose published heads do)
ON_THE_KERNELS = {
    "afmoe": dict(head_dim=128, seq_len=128),
    "mla_moe": dict(qk_nope_head_dim=96, qk_rope_head_dim=32, v_head_dim=128,
                    seq_len=128),
    "mellum_moe": dict(head_dim=128, seq_len=128),
}
#: model -> what makes ``Config.tiny()`` one layer with no attention
NO_ATTENTION_LAYER = {
    "granite_hybrid": dict(layer_types=("mamba",)),
    "lfm2_moe": dict(layer_types=("conv",), num_dense_layers=1),
    "kimi_linear": dict(num_hidden_layers=1, kda_layers=(1,),
                        full_attn_layers=()),
}


def _gradient(name: str, config):
    """``(jitted gradient of the model's loss, its parameters)`` on one
    example row, as the Trainer's step differentiates it."""
    lib = zoo.get_model(name)
    module = lib.make_model(config)
    batch = lib.example_batch(config, 1, seq_len=config.seq_len)
    variables = dict(module.init(jax.random.PRNGKey(0), batch["tokens"],
                                 batch["segment_ids"]))
    params = variables.pop("params")
    loss_fn = lib.make_loss_fn(module, config)
    if getattr(loss_fn, "stateful", False):
        return jax.jit(jax.grad(
            lambda p: loss_fn(p, variables, batch)[0])), params
    return jax.jit(jax.grad(lambda p: loss_fn(p, batch))), params


def _kernel_calls(jaxpr, kernel: str) -> int:
    """The ``pallas_call`` equations named ``kernel`` in ``jaxpr`` and the
    jaxprs inside it (the printed text writes a body that is called twice
    once)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += kernel in str(eqn.params.get("name_and_src_info",
                                              eqn.params.get("name")))
        else:
            n += sum(_kernel_calls(sub, kernel)
                     for sub in jax.core.jaxprs_in_params(eqn.params))
    return n


@pytest.mark.parametrize("name,fused", [
    *((name, False) for name in sorted(ONE_ATTENTION_LAYER)),
    *((name, True) for name in sorted(ON_THE_KERNELS))])
def test_a_layers_recomputation_keeps_what_attention_names(name, fused,
                                                           monkeypatch):
    """``run_layer`` recomputes a layer in the backward pass but for
    ``packed_rows.ATTENTION_SAVED`` — attention's output and its
    log-sum-exp, which both executions' forward rules name —, so the forward
    blocks run once in a gradient and not twice: with the names taken out
    of the policy the lowered gradient holds more loops (the ``jnp`` form)
    or a second forward kernel (the kernels, traced with a TPU in the
    backend's place), the backward pass is one either way, and the gradient
    is the same (what is kept is what the second run made)."""
    lib = zoo.get_model(name)
    config = dataclasses.replace(lib.Config.tiny(),
                                 **ONE_ATTENTION_LAYER[name],
                                 **(ON_THE_KERNELS[name] if fused else {}))
    if fused:
        monkeypatch.setattr(kernels, "backend", lambda: "tpu")
    assert lib.batch_counters(
        {"segment_ids": np.zeros((1, config.seq_len), np.int32)}, config)[
            "attention_fused_steps_total"] == int(fused)

    def passes():
        grad, params = _gradient(name, config)
        if fused:
            jaxpr = jax.make_jaxpr(grad)(params).jaxpr
            assert _kernel_calls(jaxpr, "attention_backward") == 1
            return _kernel_calls(jaxpr, "attention_forward"), None
        return (grad.lower(params).as_text().count("stablehlo.while"),
                grad(params))

    kept, ours = passes()
    monkeypatch.setattr(packed_decoder, "ATTENTION_SAVED", ())
    again, theirs = passes()
    assert again > kept > 0
    if fused:
        assert (kept, again) == (1, 2)
        return
    largest = max(float(abs(g).max()) for g in jax.tree_util.tree_leaves(ours))
    assert largest > 0
    for key, g in ours.items():
        assert float(abs(g - theirs[key]).max()) <= 1e-6 * largest, key


@pytest.mark.parametrize("name", sorted(NO_ATTENTION_LAYER))
def test_a_layer_with_no_attention_lowers_to_the_text_it_had(name,
                                                             monkeypatch):
    """A layer that names nothing of attention's (Mamba-2, the short
    convolution, KDA) lowers under ``run_layer``'s policy to the text it
    lowers to under the policy of the model's own names alone, which is
    none at all where the model names nothing."""
    lib = zoo.get_model(name)
    config = dataclasses.replace(lib.Config.tiny(),
                                 **NO_ATTENTION_LAYER[name])

    def text():
        grad, params = _gradient(name, config)
        return grad.lower(params).as_text()

    ours = text()
    assert "stablehlo.while" in ours
    saved = lib.make_model.__self__.saved
    # (granite keeps its feed-forwards' products, Kimi Linear its scan's)
    assert bool(saved) == (name != "lfm2_moe")
    keep, checkpoint = (jax.checkpoint_policies.save_only_these_names,
                        jax.checkpoint)
    given = []
    # (``run_layer`` alone gives ``jax.checkpoint`` a policy)
    monkeypatch.setattr(jax, "checkpoint", lambda f, policy=None: (
        given.append(policy), checkpoint(
            f, policy=keep(*saved) if policy and saved else None))[1])
    assert text() == ours
    assert sum(policy is not None for policy in given) == 1


@pytest.mark.parametrize("name", ["afmoe", "kimi_linear", "lfm2_moe",
                                  "mla_moe"])
def test_a_name_no_policy_lists_lowers_to_nothing(name, monkeypatch):
    """``packed_rows.swiglu`` names its two wide products' results
    (``SWIGLU_SAVED``) for the decoder that keeps them, granite's.  The
    four expert decoders reach it too — a dense layer's feed-forward, a
    shared expert — and list neither name, so the gradient of their tiny
    step lowers to the text it lowers to with those two names never
    given."""
    import re

    import jax.ad_checkpoint

    from tensorflowonspark_tpu.models import packed_rows

    lib = zoo.get_model(name)
    config = lib.Config.tiny()
    assert not set(packed_rows.SWIGLU_SAVED) & set(
        lib.make_model.__self__.saved)

    def text():
        # a lowered function's name ends in a running number that counts
        # the equations before it, a name's own too: each function is called
        # here by the order in which the text first mentions it
        grad, params = _gradient(name, config)
        seen = {}
        return re.sub(r"@[\w.]+", lambda m: seen.setdefault(
            m.group(), f"@f{len(seen)}"), grad.lower(params).as_text())

    ours = text()
    assert "stablehlo.dot_general" in ours
    named, name_it = [], jax.ad_checkpoint.checkpoint_name

    def name_all_but_swiglus(x, given):
        if given not in packed_rows.SWIGLU_SAVED:
            return name_it(x, given)
        named.append(given)
        return x

    # (every site imports the function when it runs)
    monkeypatch.setattr(jax.ad_checkpoint, "checkpoint_name",
                        name_all_but_swiglus)
    assert text() == ours
    assert set(named) == set(packed_rows.SWIGLU_SAVED)
