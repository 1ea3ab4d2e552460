"""Test harness configuration.

Mirrors the reference's test strategy (SURVEY.md §4): multi-node behavior is
exercised on one machine.  "TPU" in tests = the JAX CPU backend with 8 forced
host devices (``xla_force_host_platform_device_count``) — the TPU-world
analogue of the reference running Spark ``local-cluster[N,...]``.

The env vars below are set *before* jax is imported and are inherited by
spawned executor processes, where
``tensorflowonspark_tpu.util.ensure_jax_platform`` applies the virtual
device count.

The persistent compile cache is on by default in the program; the suite opts
out (``TFOS_COMPILE_CACHE=0``) so hit/miss counters and zero-new-signature
assertions see no disk cache, and the tests that exercise the cache switch it
on for themselves.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TFOS_HOST_DEVICE_COUNT", "8")
os.environ.setdefault("TFOS_NUM_CHIPS", "0")  # no real chips in unit tests
os.environ.setdefault("TFOS_COMPILE_CACHE", "0")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tensorflowonspark_tpu import util  # noqa: E402

util.ensure_jax_platform()
