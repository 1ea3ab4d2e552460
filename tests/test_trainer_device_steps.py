"""``Trainer``'s completion watcher (PR 37): the ``trainer.h2d`` and
``trainer.device_step`` spans it writes to the ring from its own threads, and
what it must not do — exist under ``TFOS_TRACE=0`` or for ``predict``, outlive
its ``Trainer``, hold a batch's arrays, change a step's result."""

import gc
import os
import sys
import threading
import time
import weakref

import jax
import numpy as np
import pytest

from tensorflowonspark_tpu import obs, trainer as trainer_mod
from tensorflowonspark_tpu.trainer import Trainer

WATCHERS = ("tfos-trainer-h2d", "tfos-trainer-device-step")
AFTER = {"prev", "dispatch", "input"}


def _trainer(**kwargs):
    return Trainer("mnist_mlp", devices=jax.devices()[:1], **kwargs)


def _batch(trainer, seed=0, rows=16):
    example = trainer.module_lib.example_batch(trainer.config,
                                               batch_size=rows)
    rng = np.random.default_rng(seed)
    return {k: (np.asarray(v) if k == "label" else
                rng.standard_normal(np.shape(v)).astype(np.float32))
            for k, v in example.items()}


def _watcher_threads():
    return [t for t in threading.enumerate() if t.name in WATCHERS]


class _Ring:
    """The spans this process's ring took after the object was made."""

    def __init__(self):
        self.t0_us = time.time() * 1e6

    def spans(self, name):
        return sorted((e for e in obs.get_tracer().snapshot()
                       if e["name"] == name and e["pid"] == os.getpid()
                       and e["ts"] >= self.t0_us), key=lambda e: e["ts"])


def _counters():
    return obs.get_registry().snapshot()["counters"]


def _count_clock_reads(monkeypatch):
    """``trainer.py``'s ``time.time()`` reads on the main thread from here
    on, one list entry each (the watcher's threads read theirs uncounted)."""
    reads = []
    real = time.time

    class _Clock:
        def __getattr__(self, name):
            return getattr(time, name)

        @staticmethod
        def time():
            if threading.current_thread() is threading.main_thread():
                reads.append(1)
            return real()

    monkeypatch.setattr(trainer_mod, "time", _Clock())
    return reads


@pytest.fixture
def stepped():
    """Five steps on host batches, then three on batches staged ahead with
    ``Trainer.shard``; the watcher drained."""
    ring = _Ring()
    before = _counters()
    trainer = _trainer()
    first = trainer._steps_done + 1
    batches = [_batch(trainer, seed) for seed in range(5)]
    losses = [float(trainer.step(b)) for b in batches]
    staged = [trainer.shard(b) for b in batches[:3]]
    losses += [float(trainer.step(s)) for s in staged]
    trainer._watcher.close()
    after = _counters()
    return {"ring": ring, "trainer": trainer, "first": first,
            "losses": losses, "batches": batches,
            "grew": {k: after.get(k, 0) - before.get(k, 0) for k in after}}


def test_one_device_step_a_step_with_its_number(stepped):
    steps = stepped["ring"].spans("trainer.device_step")
    assert [e["attrs"]["step"] for e in steps] == list(range(
        stepped["first"], stepped["first"] + 8))
    named = stepped["ring"].spans("trainer.step")
    assert [e["attrs"]["step"] for e in named] == [
        e["attrs"]["step"] for e in steps]


def test_device_steps_do_not_overlap_and_end_in_order(stepped):
    steps = stepped["ring"].spans("trainer.device_step")
    ends = [e["ts"] + e["dur"] for e in steps]
    assert all(b > a for a, b in zip(ends, ends[1:])), ends
    for prev_end, e in zip(ends, steps[1:]):
        assert e["ts"] >= prev_end - 1.0, (prev_end, e)   # µs, float rounding
    assert all(e["dur"] >= 0 for e in steps)


def test_device_step_says_what_began_it(stepped):
    for e in stepped["ring"].spans("trainer.device_step"):
        attrs = e["attrs"]
        assert attrs["after"] in AFTER, attrs
        assert attrs["input_wait_s"] >= 0 and attrs["dispatch_s"] > 0
        if attrs["after"] != "input":
            # the batch was there when the step was dispatched, or nothing
            # but its lateness could have begun the step
            assert attrs["input_wait_s"] == 0 or attrs["after"] == "prev"
    dispatch = {e["attrs"].get("parent"): e for e in
                stepped["ring"].spans("trainer.dispatch")}
    assert dispatch       # the span whose wall ``dispatch_s`` repeats


def test_device_step_ends_after_its_dispatch_began(stepped):
    steps = {e["attrs"]["step"]: e
             for e in stepped["ring"].spans("trainer.device_step")}
    named = {e["attrs"]["step"]: e
             for e in stepped["ring"].spans("trainer.step")}
    for step, e in steps.items():
        assert e["ts"] + e["dur"] >= named[step]["ts"]
        assert e["ts"] >= named[step]["ts"] - 1.0 or \
            e["attrs"]["after"] == "prev"


def test_one_h2d_a_host_batch_and_none_for_a_staged_one(stepped):
    # five host batches stepped + three staged by Trainer.shard; the three
    # steps on staged batches moved nothing
    transfers = stepped["ring"].spans("trainer.h2d")
    assert len(transfers) == 8
    nbytes = sum(int(v.nbytes) for v in stepped["batches"][0].values())
    assert {e["attrs"]["bytes"] for e in transfers} == {nbytes}
    assert all(e["dur"] > 0 for e in transfers)


def test_a_staged_batch_brings_its_arrival_to_its_step():
    ring = _Ring()
    trainer = _trainer()
    trainer.step(_batch(trainer))           # compiled, warm
    staged = trainer.shard(_batch(trainer, 1))
    trainer._watcher._transfers.put(None)   # the transfer's end is on record
    trainer._watcher._threads[0].join(10.0)
    (arrival,) = trainer._staged_arrivals.values()
    assert arrival.done.is_set() and arrival.t1 >= arrival.t0
    time.sleep(0.02)
    trainer.step(staged)
    trainer._watcher.close()
    last = ring.spans("trainer.device_step")[-1]["attrs"]
    assert last["input_wait_s"] == 0 and last["after"] != "input"
    del staged
    gc.collect()
    assert not trainer._staged_arrivals     # it went with the arrays


def test_late_steps_are_never_more_than_steps_and_no_counter_is_new(stepped):
    """A late step is one whose span says so (``input_wait_s`` > 0): the
    watcher keeps no counter of its own, since nothing would read one."""
    grew = stepped["grew"]
    assert grew["trainer_steps_total"] == 8
    late = sum(1 for e in stepped["ring"].spans("trainer.device_step")
               if e["attrs"]["input_wait_s"] > 0)
    assert 0 <= late <= grew["trainer_steps_total"]
    assert not [k for k in grew if "h2d" in k or "input_late" in k]


def test_predict_starts_no_watcher_and_writes_no_transfer():
    """An inference-only process pays nothing for the device's side of a
    step it never makes."""
    ring = _Ring()
    before = len(_watcher_threads())
    trainer = _trainer()
    batch = _batch(trainer)
    trainer.predict(batch)
    trainer.predict(trainer.shard(batch))   # the feed's call does record
    trainer._watcher.close()
    assert len(ring.spans("trainer.h2d")) == 1
    assert not ring.spans("trainer.device_step")
    del trainer
    gc.collect()
    assert len(_watcher_threads()) == before


def test_a_staged_batch_without_an_arrival_is_not_walked_again(monkeypatch):
    """A batch that passes through ``shard_batch`` whole and brought no
    arrival (staged by other hands) costs its step no second walk."""
    import jax

    trainer = _trainer()
    batch = _batch(trainer)
    trainer.step(batch)
    ring = _Ring()
    staged = jax.device_put(trainer.shard(batch))
    trainer._staged_arrivals.clear()
    monkeypatch.setattr(trainer._watcher, "staged", lambda *a: 1 / 0)
    trainer.step(staged)
    trainer._watcher.close()
    (span,) = [e for e in ring.spans("trainer.device_step")
               if e["attrs"]["step"] == trainer._steps_done]
    assert span["attrs"]["after"] in ("prev", "dispatch")
    assert span["attrs"]["input_wait_s"] == 0


def test_no_watcher_while_the_ring_does_not_record(monkeypatch):
    """``TFOS_TRACE=0``: no thread, no queue, no clock read of the
    watcher's, and none of its calls."""
    monkeypatch.setattr(obs.get_tracer(), "enabled", False)

    def never(*_a, **_k):
        raise AssertionError("the watcher was touched")

    monkeypatch.setattr(trainer_mod, "_DeviceWatcher", never)
    ring = _Ring()
    before = len(_watcher_threads())
    trainer = _trainer()
    batch = _batch(trainer)
    reads = _count_clock_reads(monkeypatch)
    staged = trainer.shard(batch)
    assert reads == []                      # ``shard`` read no clock
    trainer.step(staged)
    trainer.step(batch)
    assert len(reads) == 2                  # the heartbeat gauge's, a step
    assert trainer._watcher is None and not trainer._staged_arrivals
    assert len(_watcher_threads()) == before
    assert not ring.spans("trainer.device_step")
    assert not ring.spans("trainer.h2d")


def test_the_ring_on_costs_one_clock_read_a_staged_batch(monkeypatch):
    trainer = _trainer()
    batch = _batch(trainer)
    trainer.step(batch)
    reads = _count_clock_reads(monkeypatch)
    staged = trainer.shard(batch)
    assert len(reads) == 1
    trainer.step(staged)
    assert len(reads) == 2                  # + the heartbeat gauge's
    trainer._watcher.close()


def test_no_thread_is_left_once_the_trainer_is_collected():
    before = set(_watcher_threads())
    trainer = _trainer()
    trainer.step(_batch(trainer))
    mine = set(_watcher_threads()) - before
    assert sorted(t.name for t in mine) == sorted(WATCHERS)
    assert all(t.daemon for t in mine)
    ref = weakref.ref(trainer)
    del trainer
    gc.collect()
    assert ref() is None                    # the threads held no trainer
    for thread in mine:
        thread.join(10.0)
        assert not thread.is_alive()


def test_the_watcher_lets_a_batch_go_once_it_is_ready():
    trainer = _trainer()
    staged = trainer.shard(_batch(trainer))
    refs = [weakref.ref(leaf) for leaf in jax.tree_util.tree_leaves(staged)]
    trainer.step(staged)
    jax.block_until_ready(trainer.state.params)
    del staged
    # the watcher is still running (not closed): it lets go by itself, as
    # soon as its thread has seen the arrays ready
    deadline = time.monotonic() + 10.0
    while any(r() is not None for r in refs) and time.monotonic() < deadline:
        time.sleep(0.01)
        gc.collect()
    assert [r() for r in refs] == [None] * len(refs)
    assert all(t.is_alive() for t in trainer._watcher._threads)
    trainer._watcher.close()


def test_results_are_bit_identical_with_and_without_the_watcher(monkeypatch):
    def run(enabled):
        monkeypatch.setattr(obs.get_tracer(), "enabled", enabled)
        trainer = _trainer(seed=3)
        losses = []
        for seed in range(3):
            batch = _batch(trainer, seed)
            losses.append(np.asarray(trainer.step(
                batch if seed != 1 else trainer.shard(batch))))
        assert (trainer._watcher is not None) == enabled
        state = [np.asarray(leaf) for leaf in
                 jax.tree_util.tree_leaves(trainer.state)]
        if enabled:
            trainer._watcher.close()
        return losses, state

    with_losses, with_state = run(True)
    without_losses, without_state = run(False)
    for a, b in zip(with_losses + with_state, without_losses + without_state):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_the_watchdogged_step_hands_the_same_records_over():
    ring = _Ring()
    trainer = _trainer(step_timeout_s=60.0)
    first = trainer._steps_done + 1
    for seed in range(3):
        trainer.step(_batch(trainer, seed))
    trainer._watcher.close()
    steps = ring.spans("trainer.device_step")
    assert [e["attrs"]["step"] for e in steps] == [first, first + 1, first + 2]
    assert len(ring.spans("trainer.h2d")) == 3
    # the loss was forced inside the dispatch: the device's step lies in it
    for e in steps[1:]:
        assert e["dur"] * 1e-6 <= e["attrs"]["dispatch_s"] + 0.05


def test_a_full_queue_takes_no_more_and_holds_nothing(monkeypatch):
    monkeypatch.setattr(trainer_mod._DeviceWatcher, "MAX_PENDING", 0)
    ring = _Ring()
    trainer = _trainer()
    batch = _batch(trainer)
    loss = trainer.step(trainer.shard(batch))
    assert np.isfinite(float(loss))
    watcher = trainer._watcher
    assert watcher._transfers.qsize() == 0 and watcher._steps.qsize() == 0
    assert not trainer._staged_arrivals
    watcher.close()
    assert not ring.spans("trainer.device_step")
    assert not ring.spans("trainer.h2d")


def test_a_failed_wait_ends_no_thread():
    """An array that never becomes ready (deleted under the watcher) costs
    its span and nothing else."""
    ring = _Ring()
    trainer = _trainer()
    batch = _batch(trainer)
    trainer.step(batch)
    staged = trainer.shard(batch)
    gone = jax.device_put(np.ones(3, np.float32))
    gone.delete()
    watcher = trainer._watcher
    arrival = trainer_mod._Arrival(time.time())
    watcher._transfers.put((arrival, [gone], 12))
    watcher.stepped(10 ** 6, time.time(), 0.001, arrival, gone)
    trainer.step(staged)
    watcher.close()
    assert arrival.done.is_set() and arrival.t1 is None
    steps = [e["attrs"]["step"] for e in ring.spans("trainer.device_step")]
    assert 10 ** 6 not in steps and len(steps) == 2
    assert len(ring.spans("trainer.h2d")) == 2


def test_staging_from_many_threads_loses_no_record():
    """More staging threads than cores, a short switch interval: every host
    batch staged has its ``trainer.h2d``, every step its
    ``trainer.device_step``, and one watcher serves them all."""
    ring = _Ring()
    trainer = _trainer()
    batch = _batch(trainer)
    trainer.step(batch)
    workers, each = 8, 12
    staged, errors = [], []
    lock = threading.Lock()

    def stage():
        try:
            for _ in range(each):
                item = trainer.shard(batch)
                with lock:
                    staged.append(item)
        except BaseException as e:      # surfaced below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=stage) for _ in range(workers)]
        for t in threads:
            t.start()
        stepped = 0
        deadline = time.monotonic() + 60.0
        while (stepped < workers * each and time.monotonic() < deadline):
            with lock:
                item = staged.pop() if staged else None
            if item is None:
                time.sleep(0.001)
                continue
            trainer.step(item)
            stepped += 1
        for t in threads:
            t.join(30.0)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert not errors and stepped == workers * each
    trainer._watcher.close()
    assert len(ring.spans("trainer.h2d")) == 1 + workers * each
    steps = ring.spans("trainer.device_step")
    assert len(steps) == 1 + workers * each
    assert len({e["attrs"]["step"] for e in steps}) == len(steps)
    assert len({e["tid"] for e in steps}) == 1
