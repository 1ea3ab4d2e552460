"""The one seam between an algorithm and its Pallas kernels.

Five pieces of the decoders trained on packed rows are one algorithm with
two executions — kernels on a TPU at shapes that fill their tiles, ``jnp``
code (the kernels' oracle) anywhere else.  Site (the counters' name), where
its rule and dispatch are, its kernels: ``attention`` and ``conv``,
``packed_rows``, ``attention_pallas`` and ``conv_pallas``; ``ssm_scan``,
``granite_hybrid``, ``ssd_pallas``; ``kda_scan``, ``kimi_linear``,
``kda_pallas``; ``moe_grouped``, ``parallel/moe.py``,
``parallel/grouped_pallas``.

Every rule is :func:`runs_fused` of the kernels' module and the shapes,
every pair of counters :func:`step_counters` of its answer, and the backend
all of them read is :func:`backend`: the one name a test patches to compile
for a described chip.  What the five kernel modules wrote alike is here too
(:func:`compiler_params`, :func:`jitted`, :func:`dot`).

A leaf: it imports nothing of the package (``parallel/moe.py`` and the
kernel modules, which ``packed_rows`` imports lazily, both import it), and
JAX only where it is used.
"""

from __future__ import annotations

import functools


def backend() -> str:
    """The backend the process computes on (a compile test for a described
    chip, on a CPU host, says "tpu" here)."""
    import jax

    return jax.default_backend()


def runs_fused(kernels, *shapes, when: bool = True) -> bool:
    """Whether a site executes on the Pallas kernels of the module
    ``kernels`` (True) or as ``jnp`` code (False), decided from what the
    code can observe: the backend is a TPU, ``kernels.fits(*shapes)`` (whole
    lanes, whole tiles of the kernels' own) and ``when``, the site's own
    condition (the values as wide as the keys, a bias the kernels take, the
    form of the routed part that is not the overflow one)."""
    return bool(when) and backend() == "tpu" and kernels.fits(*shapes)


def step_counters(name: str, fused: bool, present: bool = True) -> dict:
    """What one step adds to the program's counters for the site ``name``:
    one step on the kernels (``fused``) or as ``jnp`` code, the other named
    with 0 so that both are on the record; both 0 for a model with no such
    layer (``present``)."""
    return {f"{name}_fused_steps_total": int(present and fused),
            f"{name}_plain_steps_total": int(present and not fused)}


def compiler_params(vmem_limit_bytes=None):
    """A grid whose first axis is parallel and whose second carries state."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=vmem_limit_bytes)


def dot(a, b, contract):
    """``a`` and ``b`` contracted over one axis each (``contract``: the
    axis of each), accumulated in float32; inside a kernel."""
    import jax
    import jax.numpy as jnp

    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


@functools.lru_cache(maxsize=None)
def jitted(call, static_argnums=()):
    """A kernel call under ``jax.jit``, made once (the modules import JAX
    only when they are used): a model calls a kernel once a layer and pass,
    and a jitted function's body — a block of heads or tiles unrolled — is
    traced and lowered once a shape, not once a call (granite's step trace
    fell from 16 s to 6)."""
    import jax

    return jax.jit(call, static_argnums=static_argnums)
