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

#: A test whose last lines an accepted later PR makes stale, in a file that
#: only a ``benchmark`` PR may edit (``BENCHMARK.json`` lists
#: ``tests/benchmark_checks`` under ``paths``): it holds that PR 40's entries
#: are the *last* of ``BENCHMARK.json``'s lists, and the contract has every
#: later cell append to them.  Everything else it holds stays enforced, in
#: ``test_benchmark_kimi_linear.py``: the lists' order counted from the front
#: (``..._entries_follow_the_accepted_ones_in_their_order``) and, as one case
#: of ``test_benchmark_cell_reports_its_own_metrics_and_has_its_limits``,
#: lfm2's own metrics, package and limits.  Strict and for an assertion
#: alone: once a ``benchmark`` PR repairs the three lines the mark fails the
#: run until it is taken out (ROADMAP C16).
STALE = {
    "test_benchmark_lfm2.py::"
    "test_benchmark_lfm2_entries_pass_the_contracts_static_rules":
        "asserts that lfm2_8b_a1b's entries end BENCHMARK.json's lists; "
        "PR 43 appended kimi_linear_48b_a3b's, as the contract wants",
}


def pytest_collection_modifyitems(config, items):
    import pytest

    for item in items:
        for tail, reason in STALE.items():
            if item.nodeid.endswith(tail):
                item.add_marker(pytest.mark.xfail(
                    reason=reason, raises=AssertionError, strict=True))
