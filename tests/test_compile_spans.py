"""JAX's compile path in the ring (PR 53): the ``jit.trace`` / ``jit.lower`` /
``jit.compile`` spans and the two counters ``compile_cache.py``'s time-span
listener writes from JAX's own events — where they lie at a ``Trainer``'s
start, what a steady step and ``TFOS_TRACE=0`` leave of them, what the
persistent cache's three states read as, and that the listener cannot fail a
compile."""

import contextlib
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu import compile_cache, obs
from tensorflowonspark_tpu.trainer import Trainer

NEW_COUNTERS = ("jit_traces_total", "compile_cache_disk_misses_total")
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


def _trainer():
    return Trainer("mnist_mlp", devices=jax.devices()[:1])


def _batch(trainer, rows=16):
    example = trainer.module_lib.example_batch(trainer.config,
                                               batch_size=rows)
    return {k: np.asarray(v) for k, v in example.items()}


def _counters():
    snap = obs.get_registry().snapshot()["counters"]
    return {name: snap.get(name, 0) for name in NEW_COUNTERS}


class _Ring:
    """The ``jit.*`` and trainer spans this process's ring took after the
    object was made, by start."""

    def __init__(self):
        self.t0_us = time.time() * 1e6

    def spans(self, name):
        return sorted((e for e in obs.get_tracer().snapshot()
                       if e["name"].startswith(name) and e.get("ph") == "X"
                       and e["pid"] == os.getpid()
                       and e["ts"] >= self.t0_us), key=lambda e: e["ts"])


def _inside(inner, outer, slack_us=1.0):
    return (inner["ts"] >= outer["ts"] - slack_us and inner["ts"]
            + inner["dur"] <= outer["ts"] + outer["dur"] + slack_us)


def _start(steps=2):
    """A ``Trainer`` and its first ``steps`` steps: the ring and the new
    counters after each, and every trace JAX itself reported meanwhile."""
    traces = []

    def on_span(event, start, end, **kw):
        if event == TRACE_EVENT:
            traces.append(end - start)

    jax.monitoring.register_event_time_span_listener(on_span)
    try:
        ring, counted = _Ring(), [_counters()]
        trainer = _trainer()
        batch = _batch(trainer)
        jit_spans = []
        for _ in range(steps):
            jax.block_until_ready(trainer.step(batch))
            counted.append(_counters())
            jit_spans.append(len(ring.spans("jit.")))
    finally:
        jax.monitoring.unregister_event_time_span_listener(on_span)
    return {"ring": ring, "counted": counted, "traces": traces,
            "jit_spans": jit_spans}


@pytest.fixture(scope="module")
def started():
    return _start()


@pytest.fixture()
def cache_dir(tmp_path, monkeypatch):
    """The persistent cache on at a directory of the test's own, placed as an
    operator places it (``tests/test_shapes.py::cache_dir_env``)."""
    d = str(tmp_path / "cc")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    monkeypatch.delenv("TFOS_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("TFOS_COMPILE_CACHE_DIR", raising=False)
    compile_cache.disable()
    jax.config.update("jax_compilation_cache_dir", d)
    yield d
    compile_cache.disable()
    jax.config.update("jax_compilation_cache_dir", None)


def _step_compile(ring):
    (found,) = [e for e in ring.spans("jit.compile")
                if e["attrs"]["fun"] == "jit(_step)"]
    return found


# -- a start ------------------------------------------------------------------


@pytest.mark.parametrize("name", ["jit.lower", "jit.compile"])
def test_the_steps_executable_lies_under_step_ones_dispatch(started, name):
    ring = started["ring"]
    dispatches = ring.spans("trainer.dispatch")
    (step_one,) = [e for e in ring.spans("trainer.step")
                   if e["attrs"]["step"] == 1]
    assert _inside(dispatches[0], step_one)
    (found,) = [e for e in ring.spans(name)
                if e["attrs"]["fun"] == "jit(_step)"]
    assert _inside(found, dispatches[0]), (found, dispatches[0])
    assert found["attrs"]["parent"] == "trainer.dispatch"
    assert found["parent_span_id"] == dispatches[0]["span_id"]
    assert found["tid"] == dispatches[0]["tid"]
    # what ``Trainer()`` compiled lies inside ``trainer.init`` in time
    (init,) = ring.spans("trainer.init")
    first = [e for e in ring.spans(name) if e["attrs"]["fun"] == "jit(_init)"]
    assert first and all(_inside(e, init) for e in first)


def test_only_long_traces_leave_a_span_and_every_trace_is_counted(started):
    spans = started["ring"].spans("jit.trace")
    floor = compile_cache.TRACE_SPAN_FLOOR_S
    assert floor == 0.005
    assert spans and all(e["dur"] >= floor * 1e6 - 1.0 for e in spans)
    assert "_step" in {e["attrs"]["fun"] for e in spans}
    long_ones = [t for t in started["traces"] if t >= floor]
    assert len(spans) == len(long_ones) < len(started["traces"])
    counted = started["counted"]
    assert (counted[-1]["jit_traces_total"] - counted[0]["jit_traces_total"]
            == len(started["traces"]))
    # a nested trace ends before the one that holds it: the step's own
    # covers, in time, every other trace under step 1's dispatch
    dispatch = started["ring"].spans("trainer.dispatch")[0]
    under = [e for e in spans if _inside(e, dispatch)]
    outer = max(under, key=lambda e: e["dur"])
    assert outer["attrs"]["fun"] == "_step"
    assert all(_inside(e, outer) for e in under)


def test_a_steady_step_records_no_jit_span_and_moves_no_counter(started):
    first, second = started["jit_spans"]
    assert first > 0 and second == first
    assert started["counted"][2] == started["counted"][1]
    dispatches = started["ring"].spans("trainer.dispatch")
    assert len(dispatches) == 2
    assert not [e for e in started["ring"].spans("jit.")
                if e["ts"] >= dispatches[1]["ts"]]


def test_trace_off_leaves_no_span_and_the_counters_still_count(monkeypatch):
    monkeypatch.setattr(obs.get_tracer(), "enabled", False)
    got = _start(steps=1)
    assert got["ring"].spans("jit.") == []
    assert got["traces"]
    assert (got["counted"][1]["jit_traces_total"]
            - got["counted"][0]["jit_traces_total"] == len(got["traces"]))


# -- the persistent cache's three states --------------------------------------


def test_cache_off_says_off_and_counts_no_miss(started):
    assert os.environ["TFOS_COMPILE_CACHE"] == "0"      # the suite's opt-out
    compiles = started["ring"].spans("jit.compile")
    assert compiles
    for e in compiles:
        assert e["attrs"]["cache"] == "off"
        assert not {"entry_bytes", "written", "retrieval_s"} & set(e["attrs"])
    counted = started["counted"]
    assert (counted[-1]["compile_cache_disk_misses_total"]
            == counted[0]["compile_cache_disk_misses_total"])


def test_a_cold_start_says_miss_and_the_next_one_hit(cache_dir):
    cold = _start(steps=1)
    miss = _step_compile(cold["ring"])["attrs"]
    assert miss["cache"] == "miss"
    assert miss["entry_bytes"] > 0 and miss["written"] == 1
    assert "retrieval_s" not in miss
    misses = [e for e in cold["ring"].spans("jit.compile")
              if e["attrs"]["cache"] == "miss"]
    assert (cold["counted"][1]["compile_cache_disk_misses_total"]
            - cold["counted"][0]["compile_cache_disk_misses_total"]
            == len(misses))
    entries = [n for n in os.listdir(cache_dir) if n.endswith("-cache")]
    assert len(entries) == sum(e["attrs"]["written"] for e in misses)

    jax.clear_caches()
    warm = _start(steps=1)
    step = _step_compile(warm["ring"])
    hit = step["attrs"]
    assert hit["cache"] == "hit" and "entry_bytes" not in hit
    # the read lies inside the compile's stretch
    assert 0 < hit["retrieval_s"] <= step["dur"] * 1e-6
    assert isinstance(hit["saved_s"], float)
    # the trainer's own two hit (a primitive's jit that an earlier test had
    # left in the process was not compiled by the cold start, and may miss)
    own = {"jit(_init)", "jit(_step)"}
    assert own <= {e["attrs"]["fun"] for e in misses}
    again = [e for e in warm["ring"].spans("jit.compile")
             if e["attrs"]["fun"] in own]
    assert [e["attrs"]["cache"] for e in again] == ["hit", "hit"]
    assert (warm["counted"][1]["compile_cache_disk_misses_total"]
            - warm["counted"][0]["compile_cache_disk_misses_total"]
            == sum(1 for e in warm["ring"].spans("jit.compile")
                   if e["attrs"]["cache"] == "miss"))
    # one compile's notes do not reach the next: a fresh function misses
    salt = np.float32(time.time() % 97.0)
    ring = _Ring()
    np.asarray(jax.jit(lambda x: jnp.tanh(x * salt) + 0.5321)(
        np.zeros((3, 5), np.float32)))
    assert ring.spans("jit.compile")[-1]["attrs"]["cache"] == "miss"


@pytest.mark.parametrize("max_size, written", [(7000, 1), (2000, 0)])
def test_written_follows_the_entrys_own_file(cache_dir, max_size, written):
    """A cache with a size limit evicts older entries to make room (the
    directory gains nothing across such a put, and the file is there) and
    refuses an entry larger than itself (a miss that wrote nothing)."""
    jax.config.update("jax_compilation_cache_max_size", max_size)
    try:
        compile_cache.ensure()
        ring, before = _Ring(), compile_cache.stats()["disk_writes"]
        for i in range(3):
            salt = np.float32(i + 1.5 + time.time() % 7.0)
            with (contextlib.nullcontext() if written
                  else pytest.warns(UserWarning, match="exceeds the maximum")):
                np.asarray(jax.jit(lambda x: jnp.tanh(x * salt) + salt)(
                    np.zeros((3, 3 + i), np.float32)))
        spans = ring.spans("jit.compile")[-3:]
    finally:
        jax.config.update("jax_compilation_cache_max_size", -1)
    assert [e["attrs"]["cache"] for e in spans] == ["miss"] * 3
    assert [e["attrs"]["written"] for e in spans] == [written] * 3
    assert all(2000 < e["attrs"]["entry_bytes"] < 7000 / 2 for e in spans)
    assert compile_cache.stats()["disk_writes"] - before == 3 * written
    entries = [n for n in os.listdir(cache_dir) if n.endswith("-cache")]
    assert len(entries) == 2 * written


# -- the listener never raises into JAX's compile path ------------------------


@pytest.mark.parametrize("broken", ["complete", "counter"])
def test_a_failing_listener_does_not_fail_the_compile(monkeypatch, broken):
    compile_cache.ensure()      # opted out or not: the listeners are there

    def boom(*args, **kwargs):
        raise RuntimeError(f"obs.{broken} is broken")

    monkeypatch.setattr(obs, broken, boom)
    salt = np.float32(1.0 + time.time() % 89.0)
    ring, before = _Ring(), _counters()
    out = jax.jit(lambda x: jnp.sin(x * salt) + 0.1234)(
        np.ones((4, 7), np.float32))
    np.testing.assert_allclose(np.asarray(out),
                               np.sin(np.ones((4, 7)) * salt) + 0.1234,
                               rtol=1e-5)
    if broken == "complete":
        assert ring.spans("jit.") == []
        assert _counters()["jit_traces_total"] > before["jit_traces_total"]
    else:
        assert _counters() == before
        # a cache-off compile counts nothing, so its span is written
        assert ring.spans("jit.compile")
