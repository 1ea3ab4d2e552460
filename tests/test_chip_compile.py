"""What a CPU box can prove about the chip path (PR 21 bring-up).

Three groups:

- **step programs compile for the real chip**: the TPU compiler is installed
  here and compiles for a *described* ``v5e:2x2`` topology with no chip
  attached (``on-chip-measurement`` guide, section 2).  Nothing runs, so
  these say nothing about results or times — they catch what the chip's
  compiler would refuse (memory, layouts, collectives) at no chip time.
  ``Trainer`` builds its mesh from ``jax.devices()`` and materialises its
  own parameters, which a described device cannot hold, so
  :func:`abstract_train_step` repeats ``Trainer.__init__``'s wiring with
  ``jax.eval_shape`` in place of arrays — the harness lives here, not as an
  option of ``Trainer``.
- **no fallback that hides the device**: chip pinning, the claim check, and
  a cluster whose trainer cannot reach its claimed chip.
- **the compile cache is placed from outside**, and ``chip_smoke.py``'s
  parent stays off JAX.
"""

import os
import subprocess
import sys

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else compiler logs in /tmp

import jax  # noqa: E402

from tensorflowonspark_tpu import (TFCluster, chip_info,  # noqa: E402
                                   compile_cache)
from tensorflowonspark_tpu import models as model_zoo  # noqa: E402
from tensorflowonspark_tpu.models import kernels as kernel_seam  # noqa: E402
from tensorflowonspark_tpu.sparkapi import LocalSparkContext  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ---------------------------------------------------------------------------
# Step programs compile for a described v5e:2x2
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this environment
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def abstract_train_step(model_name, config, devices, batch_size,
                        **example_kw):
    """``Trainer.__init__``'s wiring over ``devices`` with shapes for arrays
    (``example_kw`` goes to the model's ``example_batch`` for the batch's
    shapes: a language model's ``seq_len``).

    Returns ``(step, state, batch)``: the real compiled-step factory's
    output (the model's own where it supplies one, as wide&deep does;
    optimizer, donation, sharded update where the mesh allows), and the
    abstract train state / batch to ``step.lower`` it with.
    """
    import optax

    from tensorflowonspark_tpu.parallel import (
        build_mesh, create_train_state, make_train_step,
        param_sharding_from_metadata)
    from tensorflowonspark_tpu.parallel.train import unbox
    from tensorflowonspark_tpu.trainer import _model_inputs

    lib = model_zoo.get_model(model_name)
    mesh = build_mesh(None, devices=devices)
    model = lib.make_model(config, mesh=mesh)
    make_opt = getattr(lib, "make_optimizer", None)
    optimizer = (make_opt(config, 1e-3) if make_opt
                 else optax.adamw(1e-3))  # Trainer's default
    init_args = _model_inputs(lib.example_batch(config, batch_size=2))

    def init():
        return model.init(jax.random.PRNGKey(0), *init_args)

    def make_state():
        variables = unbox(init())
        return create_train_state(
            variables.pop("params"), optimizer, variables)

    param_shardings = param_sharding_from_metadata(
        jax.eval_shape(init)["params"], mesh)
    state = jax.eval_shape(make_state)
    batch = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((batch_size,) + a.shape[1:], a.dtype),
        lib.example_batch(config, batch_size=2, **example_kw))
    make_custom = getattr(lib, "make_sharded_train_step", None)
    if make_custom is not None:
        step = make_custom(model, config, optimizer, mesh, param_shardings,
                           state, batch)
    else:
        step = make_train_step(lib.make_loss_fn(model, config), optimizer,
                               mesh, param_shardings, state, batch)
    return step, state, batch


def _param_count(state) -> int:
    return sum(int(leaf.size)
               for leaf in jax.tree_util.tree_leaves(state.params))


def _collective_counts(lowered, compiled) -> tuple[dict, dict]:
    """Collective ops the program asks for (lowered StableHLO) and what the
    TPU compiler made of them (compiled HLO)."""
    asked, made = lowered.as_text(), compiled.as_text()
    return (
        {op: asked.count(f"stablehlo.{op}")
         for op in ("reduce_scatter", "all_gather", "all_reduce")},
        {op: made.count(f"{op}(") + made.count(f"{op}-start(")
         for op in ("reduce-scatter", "all-gather", "all-reduce")})


def _assert_sharded_exchange(step, lowered, compiled) -> None:
    """The program asks for reduce-scatter + all-gather and no all-reduce,
    as ``tests/test_collectives.py`` pins on CPU.  The installed TPU
    compiler keeps the all-gathers but folds every reduce-scatter into
    all-reduce (+ slice): a replicated bucket's scatter-then-gather pair IS
    an all-reduce, and it combines them all into one (PERF.md, PR 21) — so
    of the compiled module only "collectives are there" is asserted."""
    assert step.update_sharded and step.n_scatter_buckets > 0
    asked, made = _collective_counts(lowered, compiled)
    assert asked["reduce_scatter"] > 0 and asked["all_gather"] > 0, asked
    assert asked["all_reduce"] == 0, asked
    assert made["all-gather"] > 0, made
    assert made["reduce-scatter"] + made["all-reduce"] > 0, made


V5E_HBM_BYTES = 16 * 1024 ** 3


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_resnet50_full_width_step_compiles_for_one_v5e_chip(topo):
    """Every width of ``resnet.Config()`` (depth cut to one block a stage),
    the real optimizer and donation, batch 128, one described chip."""
    from tensorflowonspark_tpu.models import resnet

    config = resnet.Config(stage_sizes=(1, 1, 1, 1))
    step, state, batch = abstract_train_step(
        "resnet50", config, topo.devices[:1], 128)
    compiled = step.lower(state, batch).compile()
    assert 0 < _device_bytes(compiled) < V5E_HBM_BYTES
    # the widths are the published ones: 64..2048 channels, 1000 classes
    assert state.params["Dense_0"]["kernel"].shape == (2048, 1000)


@pytest.mark.slow  # ~45 s each here; the builder's by-hand rehearsal
@pytest.mark.parametrize("n_devices", [1, 4])
def test_resnet50_full_depth_step_compiles_for_v5e(topo, n_devices):
    from tensorflowonspark_tpu.models import resnet

    step, state, batch = abstract_train_step(
        "resnet50", resnet.Config(), topo.devices[:n_devices], 128)
    assert _param_count(state) == 25_557_032
    lowered = step.lower(state, batch)
    compiled = lowered.compile()
    print(f"resnet50 full depth, {n_devices} described device(s): "
          f"{compiled.memory_analysis()} "
          f"{_collective_counts(lowered, compiled)}")
    assert 0 < _device_bytes(compiled) < V5E_HBM_BYTES
    if n_devices == 4:
        _assert_sharded_exchange(step, lowered, compiled)
    else:
        assert not step.bucketed  # one data shard: nothing to exchange


def test_mnist_mlp_step_compiles_for_one_v5e_chip(topo):
    from tensorflowonspark_tpu.models import mnist

    step, state, batch = abstract_train_step(
        "mnist_mlp", mnist.Config(), topo.devices[:1], 128)
    compiled = step.lower(state, batch).compile()
    assert 0 < _device_bytes(compiled) < V5E_HBM_BYTES
    assert not step.bucketed  # one data shard: nothing to exchange


def test_mnist_mlp_sharded_update_compiles_for_four_v5e_chips(topo):
    """The default sharded-update step over a 4-device mesh of the described
    chips, through the TPU compiler."""
    from tensorflowonspark_tpu.models import mnist

    step, state, batch = abstract_train_step(
        "mnist_mlp", mnist.Config(), topo.devices, 128)
    lowered = step.lower(state, batch)
    _assert_sharded_exchange(step, lowered, lowered.compile())


def test_widedeep_cell_step_holds_no_table_shaped_scratch_on_a_v5e_chip(topo):
    """The benchmark's ``criteo_widedeep`` shapes (650,000 buckets a feature,
    batch 1,024) through the TPU compiler: the shape rule takes the
    touched-rows pass, whose program holds the tables and their
    accumulators (4.46 GB, donated and written in place) and scratch of
    under a hundredth of one table.  The full pass held a whole table of
    scratch, the dense gradient (PERF.md, PR 23)."""
    from tensorflowonspark_tpu.models import widedeep

    config = widedeep.Config(hash_buckets=650_000)
    assert widedeep.update_touches_rows(config.total_buckets, 1024 * 26)
    step, state, batch = abstract_train_step(
        "wide_deep", config, topo.devices[:1], 1024)
    compiled = step.lower(state, batch).compile()
    stats = compiled.memory_analysis()
    table_bytes = config.total_buckets * config.embed_dim * 4
    assert stats.temp_size_in_bytes < table_bytes / 100
    assert stats.alias_size_in_bytes > 2 * table_bytes  # updated in place
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_granite_scan_kernels_compile_at_the_published_widths(topo):
    """The Pallas kernels of the state-space scan (``models/ssd_pallas.py``)
    through the TPU's compiler at the shapes the benchmark runs — a row of
    8,192 tokens, 64 heads of 64, state 128, chunks of 256, bfloat16 —
    forward and gradient: interpret mode cannot refuse a misaligned slice
    or a kernel that asks for too much fast memory, this does.  Compiled,
    every kernel call still carries ``ssm_scan`` as a word of its
    ``op_name`` (``benchmark/device_scopes.py`` finds the scan by it), and
    nothing of shape chunks x heads x 256 x 256 (537 MB in float32) is
    held: the temporaries are the states and ``y``."""
    import re

    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from tensorflowonspark_tpu.models import ssd_pallas

    t, heads, p, n, chunk = 8192, 64, 64, 128, 256
    assert ssd_pallas.fits(chunk, heads, p, 1, n)
    one = SingleDeviceSharding(topo.devices[0])
    bf = jnp.bfloat16
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in (
        ((t, heads, p), bf), ((t, heads), jnp.float32),
        ((heads,), jnp.float32), ((t, 1, n), bf), ((t, 1, n), bf),
        ((t,), jnp.int32))]

    def loss(x, dt, a, b, c, seg):
        with jax.named_scope("ssm_scan"):
            y = ssd_pallas.fused_scan(x, dt, a, b, c, seg, chunk, bf)
        return jnp.sum(y * y)

    for fn, kernels in ((loss, 2), (jax.grad(loss, (0, 1, 2, 3, 4)), 4)):
        compiled = jax.jit(fn).lower(*shapes).compile()
        names = re.findall(
            r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"',
            compiled.as_text())
        assert len(names) == kernels, names
        assert all(re.search(r"\bssm_scan\b", name) for name in names), names
        tile_bytes = (t // chunk) * heads * chunk * chunk * 4
        assert compiled.memory_analysis().temp_size_in_bytes < tile_bytes


def _bounds_operands(text: str) -> list:
    """The length of every kernel call's first operand where it is a vector
    of int32: the loops' bounds made of the segment ids
    (``attention_pallas.first_key_blocks``, ``past_query_blocks``), which
    reach the kernels as scalars in SMEM."""
    import re

    return [int(n) for n in re.findall(
        r'custom_call_target="tpu_custom_call", '
        r'operand_layout_constraints=\{s32\[(\d+)\]\{0\}', text)]


def _bounds_of(t: int, kernels: list) -> list:
    """A bound a block of queries forward, a bound a block of keys
    backward."""
    from tensorflowonspark_tpu.models import attention_pallas

    size = {"attention_forward": attention_pallas.FORWARD_BLOCKS[0],
            "attention_backward": attention_pallas.BACKWARD_BLOCKS[1]}
    return [t // size[kernel] for kernel in kernels]


def test_attention_kernels_compile_at_the_published_widths(topo):
    """The Pallas kernels of ``document_attention``
    (``models/attention_pallas.py``) through the TPU's compiler at the
    shapes ``glm47_flash_packed_8k`` runs — one row of 8,192 tokens under
    ``jax.vmap``, 20 heads of 256, bfloat16 — forward and gradient: this
    refuses what interpret mode cannot (a misaligned slice, a transpose the
    chip has not, more fast memory than a kernel may use: a head's whole row
    is resident; the loops' bounds read from SMEM, one a grid step, the
    first operand of each call).  Compiled, every kernel call still carries
    ``attention`` as a word of its ``op_name`` (``benchmark/device_scopes.py`` finds
    ``mla_device_ms`` by it; the backward pass opens the scope itself), and
    no score leaves a kernel: the temporaries are ``out``, the loss's
    float32 copy of it and the gradients, each the size of an operand (84
    MB)."""
    import re

    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from tensorflowonspark_tpu.models import attention_pallas

    t, heads, hd = 8192, 20, 256
    assert attention_pallas.fits(t, hd)
    one = SingleDeviceSharding(topo.devices[0])
    bf = jnp.bfloat16
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in (
        ((1, t, heads, 1, hd), bf), ((1, t, heads, hd), bf),
        ((1, t, heads, hd), bf), ((1, t), jnp.int32))]

    def loss(q, k, v, seg):
        with jax.named_scope("attention"):
            out = jax.vmap(lambda q, k, v, seg: (
                attention_pallas.fused_attention(
                    q, k, v, seg, 1 / 16, bf, ("attention",))))(q, k, v, seg)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    for fn, kernels in ((loss, ["attention_forward"]),
                        (jax.grad(loss, (0, 1, 2)),
                         ["attention_forward", "attention_backward"])):
        compiled = jax.jit(fn).lower(*shapes).compile()
        names = re.findall(
            r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"',
            compiled.as_text())
        assert [n.split("/")[-2] for n in names] == kernels, names
        assert all(re.search(r"\battention\b", name) for name in names), names
        assert _bounds_operands(compiled.as_text()) == _bounds_of(t, kernels)
        assert (compiled.memory_analysis().temp_size_in_bytes
                < 6 * t * heads * hd * 2)


def test_window_attention_kernels_compile_at_the_published_widths(topo):
    """The same kernels with a window, at the shapes ``mellum2_packed_8k``
    runs — one row of 8,192 tokens, 32 query heads of 128 on 4 key heads,
    bfloat16, a window of 1,024 — forward and gradient through the TPU's
    compiler: the loops' bounds are ``program_id`` arithmetic with the
    window in it (a clip, a floor division) clipped by the documents' bound
    read from SMEM, which interpret mode cannot refuse; every kernel call
    carries the caller's scopes, and no score leaves a kernel."""
    import re

    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from tensorflowonspark_tpu.models import attention_pallas

    t, kv, rep, hd = 8192, 4, 8, 128
    assert attention_pallas.fits(t, hd)
    one = SingleDeviceSharding(topo.devices[0])
    bf = jnp.bfloat16
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in (
        ((1, t, kv, rep, hd), bf), ((1, t, kv, hd), bf),
        ((1, t, kv, hd), bf), ((1, t), jnp.int32))]
    scopes = ("attention", "window_attention")

    def loss(q, k, v, seg):
        with jax.named_scope("attention"), \
                jax.named_scope("window_attention"):
            out = jax.vmap(lambda q, k, v, seg: (
                attention_pallas.fused_attention(
                    q, k, v, seg, hd ** -0.5, bf, scopes, window=1024)))(
                        q, k, v, seg)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    for fn, kernels in ((loss, ["attention_forward"]),
                        (jax.grad(loss, (0, 1, 2)),
                         ["attention_forward", "attention_backward"])):
        compiled = jax.jit(fn).lower(*shapes).compile()
        names = re.findall(
            r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"',
            compiled.as_text())
        assert [n.split("/")[-2] for n in names] == kernels, names
        assert all(re.search(rf"\b{scope}\b", name) for name in names
                   for scope in scopes), names
        assert _bounds_operands(compiled.as_text()) == _bounds_of(t, kernels)
        # out, its float32 copy, the gradients; dk and dv a query head in
        # float32 before their sum over a key head's eight
        assert (compiled.memory_analysis().temp_size_in_bytes
                < 16 * t * kv * rep * hd * 2)


@pytest.mark.parametrize("rows,k,n", [
    (24576, 2048, 1792), (24576, 1792, 2048),     # lfm2_8b_a1b_packed_8k
    (12288, 2048, 1536), (12288, 1536, 2048),     # glm47_flash_packed_8k
    (32768, 2048, 1792),        # all the slots: a model with one form
    # ``moe.tight_rows`` of the four expert cells (PR 50)
    (12288, 2048, 1792), (6144, 1536, 2048),    # lfm2, glm
    (24576, 2304, 896), (3072, 1024, 2304),     # mellum2, kimi linear
])
def test_grouped_kernels_compile_at_the_published_widths(topo, rows, k, n):
    """The Pallas kernels of the routed experts' grouped products
    (``parallel/grouped_pallas.py``) through the TPU's compiler at shapes
    the two expert cells run — eight groups, bfloat16 rows against float32
    weights — forward and gradient: this refuses what interpret mode cannot
    (a grid whose length is a device scalar, a product contracted over the
    rows' axis, more fast memory than a kernel may use: a group's weights
    are resident).  Compiled, every kernel call carries ``moe_experts`` as a
    word of its ``op_name`` (``benchmark/moe_scopes.py`` finds the grouped
    products by it; the backward pass opens the scope itself), nothing is a
    ``ragged-dot`` kernel of the compiler's, and the temporaries are the
    operands' size: the result, the cast weights and the two gradients."""
    import re

    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from tensorflowonspark_tpu.parallel import grouped_pallas

    bf, groups = jnp.bfloat16, 8
    assert grouped_pallas.fits(rows, k, n, bf)
    one = SingleDeviceSharding(topo.devices[0])
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in (
        ((rows, k), bf), ((groups, k, n), jnp.float32),
        ((groups,), jnp.int32))]

    def loss(x, w, sizes):
        with jax.named_scope("moe_experts"):
            out = grouped_pallas.grouped_product(
                x, w, grouped_pallas.plan(sizes, rows), "moe_experts")
        live = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
        return jnp.sum(jnp.where(live, out, 0) ** 2)

    for fn, kernels in ((loss, ["grouped_rows"]),
                        (jax.grad(loss, (0, 1)),
                         ["grouped_rows", "grouped_rows", "grouped_weights"])):
        compiled = jax.jit(fn).lower(*shapes).compile()
        text = compiled.as_text()
        names = _pallas_calls(text)
        assert sorted(name.split("/")[-2] for name in names) == kernels, names
        assert all(re.search(r"\bmoe_experts\b", name) for name in names)
        assert "ragged-dot" not in text
        assert (compiled.memory_analysis().temp_size_in_bytes
                < 4 * (rows * (k + n) + groups * k * n) * 2)


@pytest.mark.parametrize("c,taps,form", [
    (4352, 4, "granite"),       # granite_h_micro_packed_8k: bias, SiLU
    (4096, 4, "kimi"),          # kimi_linear_packed_8k: SiLU, to float32
    (2048, 3, "lfm2"),          # lfm2_8b_a1b_packed_8k: two gates
])
def test_conv_kernels_compile_at_the_published_widths(topo, c, taps, form):
    """The Pallas kernels of ``packed_rows.causal_conv``
    (``models/conv_pallas.py``) through the TPU's compiler at the three
    shapes the benchmark runs — a row of 8,192 tokens in bfloat16, the taps
    and the bias float32 — forward and gradient: this refuses what interpret
    mode cannot (a rotation or a concatenation off the tiling, a block the
    fast memory cannot hold).  Compiled, the forward pass is one
    ``conv_forward`` call under the caller's scope and the gradient one more
    and a ``conv_backward`` under the scopes the call names (the backward
    pass opens them itself: ``benchmark/device_scopes.py`` finds the
    convolution by them), and no float32 copy of the row is held beside
    the operands: the ``jnp`` form's temporaries are five of them."""
    import re

    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from tensorflowonspark_tpu.models import conv_pallas

    t, bf, f32 = 8192, jnp.bfloat16, jnp.float32
    assert conv_pallas.fits(t, c, taps)
    one = SingleDeviceSharding(topo.devices[0])
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in (
        ((t, c), bf), ((taps, c), f32), ((c,), f32), ((t,), jnp.int32),
        ((t, c), bf), ((t, c), bf))]
    scopes = ("the_mixer", "the_conv")

    def conv(x, w, b, seg, z, g):
        how = {"granite": dict(silu=True, out=bf),
               "kimi": dict(silu=True, out=f32),
               "lfm2": dict(times=z, gate=g, out=bf)}[form]
        with packed_rows_under(scopes):
            return conv_pallas.fused_conv(
                x, w, b if form == "granite" else 0.0, seg, scopes=scopes,
                **how)

    def loss(x, w, b, seg, z, g):
        return jnp.sum(conv(x, w, b, seg, z, g).astype(f32) ** 2)

    from tensorflowonspark_tpu.models.packed_rows import (
        under as packed_rows_under)

    for fn, kernels in ((conv, ["conv_forward"]),
                        (jax.grad(loss, (0, 1, 2, 4, 5)),
                         ["conv_backward", "conv_forward"])):
        compiled = jax.jit(fn).lower(*shapes).compile()
        names = _pallas_calls(compiled.as_text())
        assert sorted(name.split("/")[-2] for name in names) == kernels, names
        assert all(re.search(rf"\b{scope}\b", name)
                   for name in names for scope in scopes), names
        assert compiled.memory_analysis().temp_size_in_bytes < 2 * t * c * 4


def test_kda_kernels_compile_at_the_published_widths(topo, monkeypatch):
    """The Pallas kernels of the chunked delta rule (``models/kda_pallas.py``)
    through the TPU's compiler at the shapes the benchmark runs — a KDA
    layer's row of 8,192 tokens, 32 heads of 128 x 128, chunks of 64,
    bfloat16 operands, the decay and ``beta`` float32 — forward and
    gradient, through ``kimi_linear.kda_scan`` with a TPU in the backend's
    place: this refuses what interpret mode cannot (a slice off the tiling,
    a cell the fast memory cannot hold).  Compiled, the forward pass is one
    ``kda_forward`` call under the caller's scopes and the gradient one more
    and a ``kda_backward`` under the scopes the call names (the backward
    pass opens them itself: ``benchmark/kda_scopes.py`` finds the scan by
    them), no loop is left, and nothing of a group's size (4 x 32 x 64 x 128
    float32, of which the ``jnp`` form holds some fifty) is held: the
    temporaries are the states entering the 64 cells and the chunks'
    inverses, in bfloat16 (and, of the gradient here, the outputs and
    their cotangent)."""
    import re

    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from tensorflowonspark_tpu.models import (kda_pallas, kimi_linear,
                                              packed_rows)

    t, heads, hd, bf, f32 = 8192, 32, 128, jnp.bfloat16, jnp.float32
    monkeypatch.setattr(kernel_seam, "backend", lambda: "tpu")
    assert kimi_linear.kda_scan_runs_fused(64, heads, hd, hd)
    one = SingleDeviceSharding(topo.devices[0])
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in (
        ((t, heads * hd), bf), ((t, heads * hd), bf), ((t, heads * hd), bf),
        ((t, heads * hd), f32), ((t, heads), f32), ((t,), jnp.int32))]
    scopes = ("kda_mixer", "kda_scan")

    def scan(q, k, v, g, beta, seg):
        with packed_rows.under(scopes):
            return kimi_linear.kda_scan(
                *(a.reshape(t, heads, hd) for a in (q, k, v, g)), beta, seg,
                64, bf, scopes)

    def loss(*a):
        return jnp.sum(scan(*a).astype(f32) ** 2)

    held = (t // kda_pallas.CELL * heads * hd * hd      # the states
            + t // 2 * heads * hd) * 2                  # the inverses
    for fn, kernels in ((scan, ["kda_forward"]),
                        (jax.grad(loss, (0, 1, 2, 3, 4)),
                         ["kda_backward", "kda_forward"])):
        compiled = jax.jit(fn).lower(*shapes).compile()
        text = compiled.as_text()
        names = _pallas_calls(text)
        assert sorted(name.split("/")[-2] for name in names) == kernels, names
        assert all(re.search(rf"\b{scope}\b", name)
                   for name in names for scope in scopes), names
        assert " while(" not in text
        assert "f32[4,32,64,128]" not in text
        assert (compiled.memory_analysis().temp_size_in_bytes
                < held + 2 * t * heads * hd * 2 + 2 ** 22)


def test_the_jnp_convolution_moves_ten_times_its_operands(topo):
    """Why ``causal_conv`` has kernels, read from the compiler with no chip
    (``pytest tests/test_chip_compile.py -k moves_ten_times -s`` prints
    the readings; PERF.md section 7): compiled alone for the described chip
    at granite's shape, the ``jnp`` form — K shifted float32 sums — moves
    more than ten times the bytes of ``x`` in and ``y`` out forward (1.71 GB
    against 0.143) and holds more than four float32 copies of the row with
    its gradient (a shift of 1 to 3 rows, along sublanes, is not fused into
    one pass); the two attempts at one ``jnp`` expression of PR 44 read 0.43
    GB forward and 2.71 with the gradient (one padded array sliced K times)
    and 2.93 / 7.43 (a grouped ``conv_general_dilated`` and a correction)."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from tensorflowonspark_tpu.models import packed_rows

    t, c, bf, f32 = 8192, 4352, jnp.bfloat16, jnp.float32
    one = SingleDeviceSharding(topo.devices[0])
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in (
        ((t, c), bf), ((4, c), f32), ((c,), f32), ((t,), jnp.int32))]

    def conv(x, w, b, seg):     # the backend is the CPU here: the jnp form
        return packed_rows.causal_conv(x, w, b, seg, silu=True, out=bf)

    def loss(x, w, b, seg):
        return jnp.sum(conv(x, w, b, seg).astype(f32) ** 2)

    forward = jax.jit(conv).lower(*shapes).compile()
    both = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
        *shapes).compile()
    moved = [m.cost_analysis()["bytes accessed"] for m in (forward, both)]
    held = both.memory_analysis().temp_size_in_bytes
    print(f"jnp causal_conv at {t} x {c}: {moved[0] / 1e9:.3f} GB forward, "
          f"{moved[1] / 1e9:.3f} GB value and gradient, "
          f"{held / 1e6:.1f} MB of temporaries")
    assert moved[0] > 10 * (2 * t * c * 2)
    assert held > 4 * t * c * 4


@pytest.mark.slow  # ~60 s here; the builder's by-hand rehearsal
def test_granite_published_width_step_fits_one_v5e_chip(topo, monkeypatch):
    """The ``granite_4_0_h_micro`` configuration as the benchmark builds it
    (one period of the published widths, an eighth of the vocabulary,
    772,160,448 float32 parameters under AdamW) on one packed row of 8,192
    tokens, through the TPU compiler: parameters and both moments are
    donated and updated in place, and arguments plus temporaries stay under
    the chip's memory with room for the staged batches.  The scan is the
    one a chip runs (the Pallas kernels: here the backend is the CPU, so
    the test says "tpu" in ``kernels.backend``'s place),
    three kernels a layer and two more in its backward pass; so is the
    mixers' convolution (``conv_pallas``: a ``conv_forward`` a layer, one
    more in its recomputation, a ``conv_backward``).  PERF.md section 4
    holds the figures."""
    import json

    from benchmark.configs.granite_4_0_h_micro import program
    from tensorflowonspark_tpu.models import packed_rows
    monkeypatch.setattr(kernel_seam, "backend", lambda: "tpu")

    with open(os.path.join(REPO, "benchmark", "configs",
                           "granite_4_0_h_micro", "config.json")) as f:
        published = json.load(f)
    config = program.model_config(published)
    step, state, batch = abstract_train_step(
        "granite_hybrid", config, topo.devices[:1], 1, seq_len=config.seq_len)
    assert batch["tokens"].shape == (1, 8192)
    assert _param_count(state) == published["parameters"] == 772_160_448
    compiled = step.lower(state, batch).compile()
    stats = compiled.memory_analysis()
    print(f"granite_4_0_h_micro, one described chip: {stats}")
    # nine mixers: states and output forward, the same again recomputed,
    # then the states kernel and the backward kernel; the convolution
    # forward, recomputed and backward, each under the mixer's scopes
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 9 * 9
    _assert_conv_kernels(text, 9, "ssm_mixer/ssm_conv")
    state_bytes = 12 * published["parameters"]
    assert stats.alias_size_in_bytes >= state_bytes     # updated in place
    assert stats.argument_size_in_bytes < state_bytes + 2 ** 20
    # the whole gradient (4 bytes a parameter) is never held at once: the
    # temporaries are under it plus what every layer's recomputation keeps
    # of its feed-forward (``packed_rows.SWIGLU_SAVED``: the two wide
    # products' results, in the activations' 2 bytes)
    kept = (len(config.layer_types) * len(packed_rows.SWIGLU_SAVED)
            * config.seq_len * config.intermediate_size * 2)
    assert kept == 2_684_354_560
    assert stats.temp_size_in_bytes < 4 * published["parameters"] + kept
    assert _device_bytes(compiled) < V5E_HBM_BYTES - 2 ** 30


def _pallas_calls(text: str) -> list:
    """The ``op_name`` of every Pallas call in a compiled module's text."""
    import re

    return re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text)


def _assert_conv_kernels(text: str, convs: int, scopes: str) -> None:
    """``text``: a compiled step of ``convs`` calls of
    ``packed_rows.causal_conv``: each is a ``conv_forward`` kernel in the
    forward pass, one more in its layer's recomputation and a
    ``conv_backward``, all under the caller's ``scopes``."""
    import re

    ours = [n for n in _pallas_calls(text) if "/conv_" in n]
    for kernel, calls in (("conv_forward", 2), ("conv_backward", 1)):
        assert sum(f"/{kernel}/" in n for n in ours) == convs * calls, ours
    assert all(re.search(rf"\b{scope}\b", n)
               for n in ours for scope in scopes.split("/")), ours


def _assert_kda_kernels(text: str, layers: int) -> None:
    """``text``: a compiled step of ``layers`` KDA layers: each is one
    ``kda_forward`` kernel — one, not two: the layer's recomputation keeps
    what the scan names and does not run the recurrence again — and one
    ``kda_backward``, all under ``kda_mixer`` and ``kda_scan``."""
    import re

    ours = [n for n in _pallas_calls(text)
            if "/kda_forward/" in n or "/kda_backward/" in n]
    for kernel in ("kda_forward", "kda_backward"):
        assert sum(f"/{kernel}/" in n for n in ours) == layers, ours
    assert all(re.search(rf"\b{scope}\b", n)
               for n in ours for scope in ("kda_mixer", "kda_scan")), ours


def _assert_grouped_kernels(text: str, layers: int) -> None:
    """``text``: a compiled step.  Each of the two forms of the routed
    part that a step takes when a layer's slots fit (``moe.tight_rows`` of
    them, or ``moe.prefix_rows``) holds twelve grouped products on the
    kernels: the forward three, the same again where the backward pass asks
    for them, the rows' gradients and the weights'; each under the
    ``moe_experts`` scope.  The overflow form
    (all the slots) keeps the compiler's own ``ragged-dot`` kernels, twelve
    a layer: it is compiled, loaded at every start and run in almost no
    step, so it is kept small (``moe.grouped_runs_fused``)."""
    import re

    ours = [n for n in _pallas_calls(text) if "/grouped_" in n]
    for kernel, calls in (("grouped_rows", 2 * 9), ("grouped_weights", 2 * 3)):
        assert sum(f"/{kernel}/" in n for n in ours) == layers * calls
    assert all(re.search(r"\bmoe_experts\b", n) for n in ours), ours
    # (the compiler drops the scopes a ``ragged_dot`` was traced under and
    # keeps its caller's: the routed part is a ``jax.jit`` of its own)
    assert len(re.findall(r'op_name="[^"]*\bragged-dot-none"',
                          text)) == layers * 12


@pytest.mark.slow  # ~2 min here; the builder's by-hand rehearsal
def test_glm_published_width_step_fits_one_v5e_chip(topo, monkeypatch):
    """The ``glm_4_7_flash`` configuration as the benchmark builds it (the
    dense layer, four expert layers with 8 of 64 experts held, the
    prediction module, an eighth of the vocabulary: 706,518,528 float32
    parameters under AdamW) on one packed row of 8,192 tokens, through the
    TPU compiler: parameters, both moments and the routing state are
    donated and updated in place, the grouped products are the ones a chip
    runs (the Pallas kernels of ``grouped_pallas`` in the form a step takes
    when its slots fit: nine ``grouped_rows`` and three ``grouped_weights``
    a layer; the compiler's own ``ragged-dot`` kernels in the overflow
    form),
    attention is the one a chip runs (the Pallas kernels: here the backend
    is the CPU, so the test says "tpu" in ``kernels.backend``'s place; a
    layer calls the forward kernel once — its recomputation keeps the
    output and the log-sum-exp by name, ``packed_rows.ATTENTION_SAVED``,
    and does not call it again — and the backward kernel once), and
    arguments plus temporaries stay under the chip's memory.  PERF.md
    section 4 holds the figures."""
    import json
    import re

    from benchmark.configs.glm_4_7_flash import program
    monkeypatch.setattr(kernel_seam, "backend", lambda: "tpu")

    with open(os.path.join(REPO, "benchmark", "configs", "glm_4_7_flash",
                           "config.json")) as f:
        published = json.load(f)
    config = program.model_config(published)
    step, state, batch = abstract_train_step(
        "mla_moe", config, topo.devices[:1], 1, seq_len=config.seq_len)
    assert batch["tokens"].shape == (1, 8192)
    assert _param_count(state) == published["parameters"] == 706_518_528
    assert state.collections["moe"]["bias"].shape == (5, 64)
    compiled = step.lower(state, batch).compile()
    stats = compiled.memory_analysis()
    print(f"glm_4_7_flash, one described chip: {stats}")
    text = compiled.as_text()
    _assert_grouped_kernels(text, layers=5)
    # the five layers share one traced routed part (``jax.jit``), and each
    # copy the compiler makes of it still carries its caller's scopes: the
    # prediction module's twelve products a size are named for it
    grouped = [n for n in _pallas_calls(text) if "/grouped_" in n]
    assert sum("mtp" in n.split("/") for n in grouped) == 24, grouped[:3]
    ours = [n for n in _pallas_calls(text) if "/attention_" in n]
    for kernel, calls in (("attention_forward", 6),
                          ("attention_backward", 6)):
        assert sum(f"/{kernel}/" in n for n in ours) == calls, ours
    assert all(re.search(r"\battention\b", n) for n in ours), ours
    state_bytes = 12 * published["parameters"]
    assert stats.alias_size_in_bytes >= state_bytes     # updated in place
    assert stats.argument_size_in_bytes < state_bytes + 2 ** 20
    # temporaries: a gradient's worth and the worst-case slot buffers
    assert stats.temp_size_in_bytes < 5 * 2 ** 30
    assert _device_bytes(compiled) < V5E_HBM_BYTES - 2 ** 30


@pytest.mark.slow  # ~2 min here; the builder's by-hand rehearsal
def test_lfm2_published_width_step_fits_one_v5e_chip(topo, monkeypatch):
    """The ``lfm2_8b_a1b`` configuration as the benchmark builds it (the
    dense conv layer, then attention, three conv layers and attention again
    with 8 of 32 experts held, a quarter of the vocabulary: 606,456,064
    float32 parameters under AdamW) on one packed row of 8,192 tokens,
    through the TPU compiler: parameters, both moments and the routing state
    are donated and updated in place, the grouped products are the ones a
    chip runs (the Pallas kernels of ``grouped_pallas``, nine
    ``grouped_rows`` and three ``grouped_weights`` a layer, in the form at
    ``moe.prefix_rows`` of the slots — a quarter share leaves that under
    all of them — and the compiler's own ``ragged-dot`` kernels in the
    overflow form), attention at heads of 64 is ``jnp`` code on a TPU too (the
    test says "tpu" in ``kernels.backend``'s place, the
    attention rule still says plain, and no attention kernel is called),
    and arguments, temporaries and code stay under 15.75 GiB.  PERF.md
    section 4 holds the figures."""
    import json

    from benchmark.configs.lfm2_8b_a1b import program
    from tensorflowonspark_tpu.models import packed_rows

    with open(os.path.join(REPO, "benchmark", "configs", "lfm2_8b_a1b",
                           "config.json")) as f:
        published = json.load(f)
    monkeypatch.setattr(kernel_seam, "backend", lambda: "tpu")
    config = program.model_config(published)
    assert not packed_rows.attention_runs_fused(config.seq_len,
                                                config.head_dim)
    step, state, batch = abstract_train_step(
        "lfm2_moe", config, topo.devices[:1], 1, seq_len=config.seq_len)
    assert batch["tokens"].shape == (1, 8192)
    assert _param_count(state) == published["parameters"] == 606_456_064
    assert state.collections["moe"]["bias"].shape == (5, 32)
    compiled = step.lower(state, batch).compile()
    stats = compiled.memory_analysis()
    print(f"lfm2_8b_a1b, one described chip: {stats}")
    text = compiled.as_text()
    _assert_grouped_kernels(text, layers=5)
    _assert_conv_kernels(text, 4, "conv_mixer/short_conv")
    assert "/attention_forward/" not in text
    state_bytes = 12 * published["parameters"]
    assert stats.alias_size_in_bytes >= state_bytes     # updated in place
    assert stats.argument_size_in_bytes < state_bytes + 2 ** 20
    # temporaries: a gradient's worth and the routed part's slot buffers
    assert stats.temp_size_in_bytes < 5 * 2 ** 30
    assert (_device_bytes(compiled) + stats.generated_code_size_in_bytes
            < V5E_HBM_BYTES - 2 ** 28)      # 15.75 GiB


@pytest.mark.slow  # ~3 min here; the builder's by-hand rehearsal
def test_kimi_linear_published_width_step_fits_one_v5e_chip(topo,
                                                            monkeypatch):
    """The ``kimi_linear_48b_a3b`` configuration as the benchmark builds it
    (the published layers 1-5: KDA with the dense SwiGLU, then KDA, KDA,
    latent attention, KDA with 8 of 256 experts held beside a shared one, an
    eighth of the vocabulary and an untied head: 602,433,408 float32
    parameters under AdamW) on one packed row of 8,192 tokens, through the
    TPU compiler: parameters, both moments and the routing state are donated
    and updated in place; the chunked recurrence runs on the kernels of
    ``kda_pallas`` (a forward and a backward call a KDA layer: the layer's
    recomputation keeps what the scan names); the grouped products are the ones a
    chip runs (the Pallas kernels of ``grouped_pallas`` in the form at
    ``moe.prefix_rows`` of the slots — 6,144 rows at a 1/32 share, 24 tiles
    of 256 — and the compiler's own ``ragged-dot`` kernels in the overflow
    form); attention at keys of 192 and values of 128 is ``jnp`` code on a
    TPU too (the test says "tpu" in ``kernels.backend``'s place, the
    attention rule still says plain, and no attention kernel
    is called), and arguments, temporaries and code stay under 15.75 GiB.
    PERF.md section 4 holds the figures."""
    import json

    from benchmark.configs.kimi_linear_48b_a3b import program
    from tensorflowonspark_tpu.models import packed_rows
    from tensorflowonspark_tpu.parallel import grouped_pallas, moe

    with open(os.path.join(REPO, "benchmark", "configs",
                           "kimi_linear_48b_a3b", "config.json")) as f:
        published = json.load(f)
    monkeypatch.setattr(kernel_seam, "backend", lambda: "tpu")
    config = program.model_config(published)
    assert not packed_rows.attention_runs_fused(config.seq_len,
                                                config.qk_head_dim)
    rows = moe.prefix_rows(8 * 8192, 8, 256)
    assert rows == 6144 and grouped_pallas.fits(rows, 2304, 1024, "bfloat16")
    step, state, batch = abstract_train_step(
        "kimi_linear", config, topo.devices[:1], 1, seq_len=config.seq_len)
    assert batch["tokens"].shape == (1, 8192)
    assert _param_count(state) == published["parameters"] == 602_433_408
    assert state.collections["moe"]["bias"].shape == (4, 256)
    compiled = step.lower(state, batch).compile()
    stats = compiled.memory_analysis()
    print(f"kimi_linear_48b_a3b, one described chip: {stats}")
    text = compiled.as_text()
    _assert_grouped_kernels(text, layers=4)
    _assert_conv_kernels(text, 4 * 3, "kda_mixer/kda_conv")
    _assert_kda_kernels(text, layers=4)
    assert "/attention_forward/" not in text
    state_bytes = 12 * published["parameters"]
    assert stats.alias_size_in_bytes >= state_bytes     # updated in place
    assert stats.argument_size_in_bytes < state_bytes + 2 ** 20
    assert (_device_bytes(compiled) + stats.generated_code_size_in_bytes
            < V5E_HBM_BYTES - 2 ** 28)      # 15.75 GiB


@pytest.mark.slow  # ~2 min here; the builder's by-hand rehearsal
def test_mellum2_published_width_step_fits_one_v5e_chip(topo, monkeypatch):
    """The ``mellum2_12b_a2_5b`` configuration as the benchmark builds it
    (the published layers 0-3: three sliding-window layers and a
    full-attention one, each with 16 of 64 softmax-routed experts held, a
    quarter of the vocabulary and an untied head: 595,154,176 float32
    parameters under AdamW) on one packed row of 8,192 tokens, through the
    TPU compiler: parameters, both moments and the routing state are donated
    and updated in place; attention at heads of 128 runs on the kernels of
    ``attention_pallas`` under grouped queries (a layer calls the forward
    kernel once — its recomputation keeps what attention names and does
    not call it again — and the backward kernel once; the three sliding
    layers' under ``window_attention``, the full one's under
    ``full_attention``, all under ``attention``); the grouped
    products are the ones a chip runs (the Pallas kernels of
    ``grouped_pallas`` in the form at ``moe.prefix_rows`` of the slots —
    49,152 rows at a quarter share of 65,536 — and the compiler's own
    ``ragged-dot`` kernels in the overflow form); and arguments,
    temporaries and code stay under 15.75 GiB.  PERF.md section 4 holds the
    figures."""
    import json
    import re

    from benchmark.configs.mellum2_12b_a2_5b import program
    from tensorflowonspark_tpu.models import packed_rows
    from tensorflowonspark_tpu.parallel import grouped_pallas, moe

    with open(os.path.join(REPO, "benchmark", "configs",
                           "mellum2_12b_a2_5b", "config.json")) as f:
        published = json.load(f)
    monkeypatch.setattr(kernel_seam, "backend", lambda: "tpu")
    config = program.model_config(published)
    assert packed_rows.attention_runs_fused(config.seq_len, config.head_dim)
    rows = moe.prefix_rows(8 * 8192, 16, 64)
    assert rows == 49152 and grouped_pallas.fits(rows, 2304, 896, "bfloat16")
    step, state, batch = abstract_train_step(
        "mellum_moe", config, topo.devices[:1], 1, seq_len=config.seq_len)
    assert batch["tokens"].shape == (1, 8192)
    assert _param_count(state) == published["parameters"] == 595_154_176
    assert state.collections["moe"]["bias"].shape == (4, 64)
    compiled = step.lower(state, batch).compile()
    stats = compiled.memory_analysis()
    print(f"mellum2_12b_a2_5b, one described chip: {stats}")
    text = compiled.as_text()
    _assert_grouped_kernels(text, layers=4)
    ours = [n for n in _pallas_calls(text) if "/attention_" in n]
    for scope, layers in (("window_attention", 3), ("full_attention", 1)):
        mine = [n for n in ours if re.search(rf"\b{scope}\b", n)]
        for kernel, calls in (("attention_forward", 1),
                              ("attention_backward", 1)):
            assert sum(f"/{kernel}/" in n for n in mine) == layers * calls, \
                (scope, kernel, mine)
    assert all(re.search(r"\battention\b", n) for n in ours), ours
    assert len(ours) == 8
    state_bytes = 12 * published["parameters"]
    assert stats.alias_size_in_bytes >= state_bytes     # updated in place
    assert stats.argument_size_in_bytes < state_bytes + 2 ** 20
    assert (_device_bytes(compiled) + stats.generated_code_size_in_bytes
            < V5E_HBM_BYTES - 2 ** 28)      # 15.75 GiB


@pytest.mark.slow  # ~2 min here; the builder's by-hand rehearsal
def test_afmoe_published_width_step_fits_one_v5e_chip(topo, monkeypatch):
    """The ``trinity_mini`` configuration as the benchmark builds it (the
    published layers 1-5: a dense layer and four expert layers with 16 of
    128 sigmoid-routed experts held beside a shared one, three sliding to
    one full, an eighth of the vocabulary and an untied head: 705,473,792
    float32 parameters under AdamW) on one packed row of 8,192 tokens,
    through the TPU compiler: parameters, both moments and the routing
    state — the gate's row among it — are donated and updated in place;
    attention at heads of 128 runs on the kernels of ``attention_pallas``
    under grouped queries, **one function two ways in one step** (the four
    sliding layers' calls under ``window_attention`` at a window of 2,048,
    the position-free full layer's under ``full_attention``; a layer calls
    the forward kernel once — its recomputation keeps what attention names
    — and the backward kernel once); a layer keeps its gate's projection
    and what each half adds before its post-norm too (``afmoe.SAVED``), so
    the routed part runs as often as a sibling's; the grouped products are
    the ones a chip runs (``grouped_pallas`` at ``moe.tight_rows`` and
    ``moe.prefix_rows`` of the slots — 24,576 rows at an eighth share of
    65,536 —, the compiler's ``ragged-dot`` in the overflow form); and
    arguments, temporaries and code stay under 15.75 GiB.  PERF.md section
    4 holds the figures."""
    import json
    import re

    from benchmark.configs.trinity_mini import program
    from tensorflowonspark_tpu.models import packed_rows
    from tensorflowonspark_tpu.parallel import grouped_pallas, moe

    with open(os.path.join(REPO, "benchmark", "configs", "trinity_mini",
                           "config.json")) as f:
        published = json.load(f)
    monkeypatch.setattr(kernel_seam, "backend", lambda: "tpu")
    config = program.model_config(published)
    assert packed_rows.attention_runs_fused(config.seq_len, config.head_dim)
    for rows in moe.row_sizes(8 * 8192, 16, 128)[:2]:
        assert grouped_pallas.fits(rows, 2048, 1024, "bfloat16"), rows
    assert moe.prefix_rows(8 * 8192, 16, 128) == 24576
    step, state, batch = abstract_train_step(
        "afmoe", config, topo.devices[:1], 1, seq_len=config.seq_len)
    assert batch["tokens"].shape == (1, 8192)
    assert _param_count(state) == published["parameters"] == 705_473_792
    assert state.collections["moe"]["bias"].shape == (4, 128)
    assert state.collections["moe"]["gate_open"].shape == (4,)
    compiled = step.lower(state, batch).compile()
    stats = compiled.memory_analysis()
    print(f"trinity_mini, one described chip: {stats}")
    text = compiled.as_text()
    _assert_grouped_kernels(text, layers=4)
    ours = [n for n in _pallas_calls(text) if "/attention_" in n]
    for scope, layers in (("window_attention", 4), ("full_attention", 1)):
        mine = [n for n in ours if re.search(rf"\b{scope}\b", n)]
        for kernel, calls in (("attention_forward", 1),
                              ("attention_backward", 1)):
            assert sum(f"/{kernel}/" in n for n in mine) == layers * calls, \
                (scope, kernel, mine)
    assert all(re.search(r"\battention\b", n) for n in ours), ours
    assert len(ours) == 10
    state_bytes = 12 * published["parameters"]
    assert stats.alias_size_in_bytes >= state_bytes     # updated in place
    assert stats.argument_size_in_bytes < state_bytes + 2 ** 20
    assert (_device_bytes(compiled) + stats.generated_code_size_in_bytes
            < V5E_HBM_BYTES - 2 ** 28)      # 15.75 GiB


def test_peak_tables_know_the_device_kind_the_chip_reports(topo):
    """``TPU v5 lite`` is what the v5e reports (chip run, PR 21) and what
    the described topology reports; both peak tables must resolve it."""
    sys.path.insert(0, REPO)
    import bench
    from tensorflowonspark_tpu.obs import roofline

    kind = topo.devices[0].device_kind
    assert kind == "TPU v5 lite"
    peak = next(p for key, p in bench.PEAK_FLOPS if key in kind.lower())
    assert peak == 197e12
    assert roofline.hbm_peak_gbps(kind) == 819.0


@pytest.mark.parametrize("n_devices", [1, 4])
def test_trainer_compiles_its_step_once(n_devices):
    """Found on the chip: the eagerly made scalars of the state (Adam's
    count, the step counter) changed placement across the first step, and
    the step compiled twice — the second time at step 2."""
    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.trainer import Trainer

    trainer = Trainer("mnist_mlp", config=mnist.Config(),
                      devices=jax.devices()[:n_devices])
    batch = trainer.shard(mnist.example_batch(mnist.Config(), batch_size=8))
    for _ in range(3):
        trainer.step(batch)
    assert trainer.train_step._jitted._cache_size() == 1


# ---------------------------------------------------------------------------
# No fallback that hides the device
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chips,bounds", [
    ([0], "1,1,1"), ([2, 3], "1,2,1"), ([0, 1, 2, 3], "2,2,1")])
def test_set_visibility_env_pins_platform_and_bounds(monkeypatch, chips,
                                                     bounds):
    for name in ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
                 "TPU_PROCESS_BOUNDS", "ALLOW_MULTIPLE_LIBTPU_LOAD"):
        monkeypatch.delenv(name, raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    chip_info.set_visibility_env(chips)
    assert os.environ["TPU_VISIBLE_CHIPS"] == ",".join(map(str, chips))
    assert os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] == bounds
    assert os.environ["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert os.environ["JAX_PLATFORMS"] == "tpu"


def test_set_visibility_env_refuses_a_claim_that_is_no_rectangle(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(ValueError, match="3 chips"):
        chip_info.set_visibility_env([0, 1, 2])
    chip_info.set_visibility_env([])  # nothing claimed: nothing pinned
    assert os.environ["JAX_PLATFORMS"] == "cpu"


def test_host_chips_counted_from_vfio_nodes_not_tpu_env(monkeypatch):
    """A one-chip machine cut from a four-chip host still says
    ``TPU_ACCELERATOR_TYPE=v5litepod-4``: only device nodes count."""
    monkeypatch.delenv("TFOS_NUM_CHIPS")
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    nodes = {"/dev/vfio/*": ["/dev/vfio/0", "/dev/vfio/vfio"],
             "/dev/accel*": []}
    monkeypatch.setattr(chip_info.glob, "glob", lambda pat: nodes[pat])
    assert chip_info.get_num_host_chips() == 1
    nodes["/dev/vfio/*"] = ["/dev/vfio/vfio"]
    assert chip_info.get_num_host_chips() == 0


def test_verify_claim_names_a_cpu_that_stood_in_for_a_chip():
    chip_info.verify_claim([])  # nothing claimed: nothing to prove
    with pytest.raises(RuntimeError, match=r"claimed TPU chips \[0\].*cpu"):
        chip_info.verify_claim([0])


def _train_on_whatever_is_there(marker_path, ctx):
    import jax.numpy as jnp

    with open(marker_path, "w", encoding="utf-8") as f:
        f.write(str(jnp.ones(2).devices()))


@pytest.mark.parametrize("mode", ["spark", "tensorflow"])
def test_claimed_chip_out_of_reach_fails_at_driver_naming_executor(
        monkeypatch, tmp_path, mode):
    """A faked one-chip claim on this chip-less box: the trainer's TPU init
    fails (the claim pinned ``JAX_PLATFORMS=tpu``), and the driver gets the
    executor's name — not a model quietly trained on the CPU."""
    monkeypatch.setenv("TFOS_NUM_CHIPS", "1")
    monkeypatch.setenv("TFOS_SCRATCH_ROOT", str(tmp_path))
    input_mode = (TFCluster.InputMode.SPARK if mode == "spark"
                  else TFCluster.InputMode.TENSORFLOW)
    trained = tmp_path / "trained"
    sc = LocalSparkContext("local-cluster[1,1,1024]", f"claim-{mode}")
    try:
        # probe off: the failure under test is the trainer's own, not the
        # probe child's (which the same pin fails first when it is on)
        with pytest.raises(RuntimeError) as err:
            cluster = TFCluster.run(
                sc, _train_on_whatever_is_there, str(trained),
                num_executors=1, input_mode=input_mode,
                num_chips_per_executor=1, health_probe=False,
                reservation_timeout=60)
            cluster.shutdown(grace_secs=30)
        text = _error_chain(err.value)
        assert "executor 0" in text
        assert "Unable to initialize backend 'tpu'" in text
        assert not trained.exists()
    finally:
        sc.stop()


def _error_chain(e: BaseException) -> str:
    parts = []
    while e is not None:
        parts.append(str(e))
        e = e.__cause__ or e.__context__
    return "\n".join(parts)


# ---------------------------------------------------------------------------
# Compile cache placed from outside; chip_smoke's parent stays off JAX
# ---------------------------------------------------------------------------


def _ensure_recording_dir_updates(monkeypatch):
    """Run ``compile_cache.ensure()`` fresh, recording every directory it
    sets on jax's config."""
    set_dirs = []
    real_update = jax.config.update

    def update(name, value):
        if name == "jax_compilation_cache_dir":
            set_dirs.append(value)
        real_update(name, value)

    monkeypatch.delenv("TFOS_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("TFOS_COMPILE_CACHE_DIR", raising=False)
    compile_cache.disable()
    monkeypatch.setattr(jax.config, "update", update)
    return compile_cache.ensure(), set_dirs


def test_cache_dir_from_env_is_left_to_jax(monkeypatch, tmp_path):
    d = str(tmp_path / "x")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    jax.config.update("jax_compilation_cache_dir", d)  # as jax's import did
    try:
        ns, set_dirs = _ensure_recording_dir_updates(monkeypatch)
        assert ns == d and set_dirs == []
        assert jax.config.jax_compilation_cache_dir == d
        assert compile_cache.active()
    finally:
        compile_cache.disable()
        jax.config.update("jax_compilation_cache_dir", None)


def test_cache_dir_defaults_to_fixed_path_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        ns, set_dirs = _ensure_recording_dir_updates(monkeypatch)
        assert ns == os.path.join(REPO, ".jax_cache") == set_dirs[-1]
        assert jax.config.jax_compilation_cache_dir == ns
    finally:
        compile_cache.disable()
    assert jax.config.jax_compilation_cache_dir is None


def test_no_cache_path_is_built_from_tempfile_pid_or_time():
    with open(os.path.join(REPO, "tensorflowonspark_tpu",
                           "compile_cache.py"), encoding="utf-8") as f:
        src = f.read()
    for needle in ("tempfile", "getpid", "time.time", "mkdtemp"):
        assert needle not in src, needle


def _run_chip_smoke(*argv, **env):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        capture_output=True, text=True, timeout=170, cwd=REPO,
        env=dict(os.environ, **env))


def test_chip_smoke_parent_imports_no_jax():
    prog = ("import sys; sys.argv = ['chip_smoke.py']\n"
            "import chip_smoke\n"
            "chip_smoke.parse_args([])\n"
            "assert 'jax' not in sys.modules, 'parent imported jax'\n"
            "print('clean')\n")
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=120, cwd=REPO)
    assert out.returncode == 0 and "clean" in out.stdout, out.stderr[-2000:]


def test_chip_smoke_refuses_a_cpu_naming_the_phase(tmp_path):
    """The sandbox rehearsal of the contract: with no chip the script
    exits non-zero, names the phase that found no TPU, and prints no
    result line."""
    out = _run_chip_smoke("--out", str(tmp_path), JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert "phase devices" in out.stderr and "cpu" in out.stderr
    for line in out.stdout.splitlines():
        assert '"ok"' not in line, line
