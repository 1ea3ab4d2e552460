"""High-level trainer: model zoo × mesh × sharded step, one object.

Reference anchor: the reference has no trainer — every example hand-writes
its TF session/estimator loop inside ``map_fun`` (``SURVEY.md §1 L6``).
Here the repeated wiring (build model, shard-init params, compile the step,
feed batches) is one class so examples, ``bench.py``, the pipeline API, and
``__graft_entry__.py`` all share a single, tested code path.

TPU-first details:

- **Sharded init**: ``jax.jit(init, out_shardings=...)`` materialises the
  parameters directly in their final sharded layout — a ResNet-50 or
  BERT-large is never fully resident on one host/device.
- The step is compiled once (static shapes); epoch loops live in Python
  *outside* jit, per XLA semantics.
- ``num_ps > 0`` (reference parameter-server knob) maps to ZeRO sharding of
  params/optimizer state over the ``fsdp`` axis (``SURVEY.md §2.3``).
"""

from __future__ import annotations

import logging
import os
import queue
import threading
import time
import weakref
from typing import Any

import numpy as np

from tensorflowonspark_tpu import models as model_zoo, obs
from tensorflowonspark_tpu.models import _model_inputs
from tensorflowonspark_tpu.parallel import (
    apply_zero_sharding,
    build_mesh,
    create_train_state,
    make_eval_step,
    make_train_step,
    mesh as mesh_lib,
    param_sharding_from_metadata,
    shard_batch,
)
from tensorflowonspark_tpu.parallel.train import TrainState, unbox

logger = logging.getLogger(__name__)


class Trainer:
    """Owns mesh, model, sharded state, and the compiled train/eval steps."""

    def __init__(
        self,
        model: str | Any,
        config: Any = None,
        mesh_config: "mesh_lib.MeshConfig | None" = None,
        optimizer: Any = None,
        learning_rate: float = 1e-3,
        zero: bool | None = None,
        seed: int = 0,
        devices: Any = None,
        step_timeout_s: float | None = None,
        error_sink: Any = None,
    ):
        import jax
        import optax

        # init is the single biggest pre-training phase (sharded init +
        # two jit compiles); span it manually rather than re-indenting the
        # whole constructor
        _t0_wall, _t0 = time.time(), time.perf_counter()

        # persistent compile cache: on by default (at
        # JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache), configured
        # BEFORE the init/step jit compiles below so a re-launched trainer
        # loads its executables from disk instead of re-paying XLA per
        # process; TFOS_COMPILE_CACHE=0 opts out
        from tensorflowonspark_tpu import compile_cache

        compile_cache.ensure()

        if isinstance(model, str):
            self.module_lib = model_zoo.get_model(model)
            self.model_name = model
        else:
            self.module_lib = model
            self.model_name = getattr(model, "__name__", None)
        self.config = config or self.module_lib.Config.tiny()
        # kept beside the mesh: the Mesh object does not record which axes
        # cross slices, and the bucketed step needs the MeshConfig to
        # stage its collectives per interconnect tier (ICI vs DCN)
        self.mesh_config = mesh_config
        self.mesh = build_mesh(mesh_config, devices=devices)
        self.model = self.module_lib.make_model(self.config, mesh=self.mesh)
        if optimizer is None:
            # a model-zoo module may prescribe its own optimizer recipe
            # (e.g. widedeep's AdaGrad-on-tables / AdamW-on-MLP split)
            make_opt = getattr(self.module_lib, "make_optimizer", None)
            optimizer = (make_opt(self.config, learning_rate) if make_opt
                         else optax.adamw(learning_rate))
        self.optimizer = optimizer
        self.sequence_axes = getattr(self.module_lib, "SEQUENCE_AXES", {})
        if self.mesh.shape.get("sp", 1) <= 1:
            self.sequence_axes = {}
        self.loss_fn = self.module_lib.make_loss_fn(self.model, self.config)
        # a model module may count what a step's host batch holds (a
        # language model's tokens and documents) and how the step it
        # compiled runs it: ``batch_counters(batch, config) -> {counter:
        # n}``, added to the registry once a step
        self._batch_counters = getattr(self.module_lib, "batch_counters",
                                       None)
        self._staged_counts: dict = {}
        # the device's side of every step (``trainer.h2d``,
        # ``trainer.device_step``): a completion watcher, made at the first
        # batch and only while the ring records; a batch a feed staged
        # waits here, under its first array, for its step to find when it
        # landed
        self._watcher: _DeviceWatcher | None = None
        self._watcher_lock = threading.Lock()
        self._staged_arrivals: dict = {}
        self.forward_fn = self.module_lib.make_forward_fn(self.model, self.config)

        # example batch sized to the data-parallel world so the compiled
        # shardings divide evenly for any mesh (dp*fsdp may be odd); a
        # pipelined model additionally splits the batch into microbatches,
        # each of which must still divide the data-parallel world
        data_world = (self.mesh.shape["dp"] * self.mesh.shape["fsdp"]
                      * self.mesh.shape.get("ep", 1))
        micro = 1
        if (getattr(self.config, "pp_stages", 0) or 0) > 1 and \
                self.mesh.shape.get("pp", 1) > 1:
            micro = max(1, getattr(self.config, "pp_microbatches", 1))
        example = self.module_lib.example_batch(
            self.config, batch_size=max(2, micro) * data_world)
        init_args = _model_inputs(example)

        # abstract init → shardings from flax partitioning metadata.
        # Non-"params" collections (BatchNorm batch_stats) replicate.
        all_shapes = jax.eval_shape(
            lambda: self.model.init(jax.random.PRNGKey(seed), *init_args)
        )
        boxed_shapes = all_shapes["params"]
        col_shapes = {k: v for k, v in all_shapes.items() if k != "params"}
        self.param_shardings = param_sharding_from_metadata(
            boxed_shapes, self.mesh
        )
        if zero is None:
            zero = self.mesh.shape.get("fsdp", 1) > 1
        if zero:
            self.param_shardings = apply_zero_sharding(
                self.param_shardings, self.mesh, unbox(boxed_shapes)
            )
        # a model module may prescribe shardings for its collections (e.g.
        # wide&deep's vocab-sharded embedding tables); others replicate
        from tensorflowonspark_tpu.parallel.train import (
            merge_collection_shardings,
        )

        mk_cs = getattr(self.module_lib, "make_collection_shardings", None)
        col_overrides = (mk_cs(self.config, self.mesh) or {}) if mk_cs else {}
        col_shardings = merge_collection_shardings(
            unbox(col_shapes), self.mesh, col_overrides)

        # sharded init: params materialise already laid out across the mesh
        def _init():
            variables = unbox(
                self.model.init(jax.random.PRNGKey(seed), *init_args)
            )
            return (variables["params"],
                    {k: v for k, v in variables.items() if k != "params"})

        params, collections = jax.jit(
            _init, out_shardings=(self.param_shardings, col_shardings)
        )()
        self.state = create_train_state(params, self.optimizer, collections)
        self._step_callbacks: list = []
        self._last_step_t: float | None = None

        # mid-run wedge watchdog (health.StepWatchdog): opt-in via the
        # step_timeout_s param or TFOS_STEP_TIMEOUT_S.  When armed, step()
        # synchronously materializes the loss so "step completed" is a
        # device-proven fact, and a stall kills the trainer process fast
        # with the reason on the node's error queue (error_sink, e.g.
        # ctx.report_error) instead of hanging the mesh until feed_timeout.
        if step_timeout_s is None:
            env_t = os.environ.get("TFOS_STEP_TIMEOUT_S")
            step_timeout_s = float(env_t) if env_t else None
        self._watchdog = None
        self._watchdog_warm_shapes: set = set()
        if step_timeout_s and step_timeout_s > 0:
            from tensorflowonspark_tpu import health

            self._watchdog = health.StepWatchdog(
                step_timeout_s, on_stall=error_sink)

        # a model-zoo module may supply its own sharded step (e.g. wide&deep's
        # sparse embedding update); it composes via parallel.train.compile_step
        make_custom = getattr(self.module_lib, "make_sharded_train_step", None)
        if make_custom is not None:
            self.train_step = make_custom(
                self.model, self.config, self.optimizer, self.mesh,
                self.param_shardings, self.state, example,
                sequence_axes=self.sequence_axes,
                collection_shardings=col_overrides or None,
            )
        else:
            self.train_step = make_train_step(
                self.loss_fn, self.optimizer, self.mesh, self.param_shardings,
                self.state, example, sequence_axes=self.sequence_axes,
                collection_shardings=col_overrides or None,
                mesh_config=self.mesh_config,
            )
        # Place the state once where the compiled step will leave it, so
        # every step (and the checkpoint template, which targets
        # self.state) sees one layout.  Two things start elsewhere.  The
        # eagerly-initialized optimizer state inherited the PARAM layout,
        # but the sharded-update step stores scatter-eligible moments as
        # dim-0 shards over the data axes.  And the eagerly made scalars
        # (Adam's count, the step counter) are uncommitted single-device
        # arrays that come back committed to the mesh: to jit a new
        # signature, so the whole step compiled a second time at step 2
        # (38 s of a cold ResNet-50 start on a v5e chip, PR 21).
        replicated = mesh_lib.replicated(self.mesh)

        def _home(leaf):
            s = leaf.sharding
            on_mesh = (isinstance(s, jax.sharding.NamedSharding)
                       and s.mesh == self.mesh)
            return s if on_mesh else replicated

        homes = jax.tree_util.tree_map(_home, self.state)
        opt_sh = getattr(self.train_step, "opt_state_shardings", None)
        if opt_sh is not None:
            homes = TrainState(homes.params, opt_sh, homes.step,
                               homes.collections)
        self.state = jax.device_put(self.state, homes)
        self.eval_step = make_eval_step(
            self.forward_fn, self.mesh, self.param_shardings,
            example, sequence_axes=self.sequence_axes,
            collections=self.state.collections,
            collection_shardings=col_overrides or None,
        )

        # ... and name what of the step's collections the counters should
        # show (what the device decided, as which expert a token went to):
        # ``device_counters(collections, config) -> {counter: cumulative
        # integer array}``, read a step late so that no step waits for it
        self._device_counters = None
        make_counts = getattr(self.module_lib, "device_counters", None)
        if make_counts is not None and self.state.collections:
            self._device_counters = _DeviceCounters(
                make_counts, self.config, self.state.collections)
            # the last step's counts are read when the trainer goes
            weakref.finalize(self, self._device_counters.drain)

        self._steps_done = 0
        # flight recorder: step() attributes its shard + dispatch
        # (compute) per step and commits the feed-plane record the
        # DataFeed's wait/ingest halves accumulated into — one bottleneck
        # verdict per training step
        self._flight = obs.flight.recorder("feed")
        # periodic checkpointing (enable via checkpoint()) and elastic
        # regroup cooperation (attach_elastic()) both ride _after_step
        self._ckpt_mgr = None
        self._ckpt_every = 0
        #: step number of the most recent periodic checkpoint request
        #: (async: the write may still be in flight; latest_step() reports
        #: only committed ones)
        self.last_checkpoint_step: int | None = None
        self._elastic = None
        #: trace id of the most recently completed step (step-scoped
        #: identity: each step records as a ``trainer.step`` span under
        #: its own trace id, so anomaly findings and bench notes can cite
        #: the exact step they judged)
        self.last_step_trace_id: str | None = None
        obs.complete("trainer.init", _t0_wall, time.perf_counter() - _t0,
                     model=self.model_name or "custom",
                     mesh=dict(self.mesh.shape))

    # -- stepping ------------------------------------------------------------

    def shard(self, batch):
        """Stage a host batch on the mesh (a feed's ``device_put``, on its
        pump's thread).  While the ring records, the transfer this starts
        is the ``trainer.h2d`` span: from here to every staged array being
        ready on the device, written by the completion watcher."""
        watcher = self._watch()
        t0 = time.time() if watcher is not None else 0.0
        staged = shard_batch(self.mesh, batch, self.sequence_axes)
        if self._batch_counters is not None and isinstance(
                _first_leaf(batch), np.ndarray):
            # counted here, where the batch is still the host's; the counts
            # wait under the staged batch's first array until ``step`` is
            # handed it, and go with that array if it never is
            _keep_under(self._staged_counts, _first_leaf(staged),
                        self._batch_counters(batch, self.config))
        if watcher is not None:
            arrival = watcher.staged(batch, staged, t0)
            if arrival is not None:
                _keep_under(self._staged_arrivals, _first_leaf(staged),
                            arrival)
        return staged

    def _watch(self):
        """The completion watcher; None while the ring does not record
        (``TFOS_TRACE=0``: no thread, no queue, no clock read)."""
        if not obs.get_tracer().enabled:
            return None
        if self._watcher is None:
            with self._watcher_lock:
                if self._watcher is None:
                    self._watcher = _DeviceWatcher()
                    # the threads end when the trainer goes; at the
                    # interpreter's exit they are daemons and nothing
                    # waits for a device that may be wedged
                    weakref.finalize(self, self._watcher.close).atexit = False
        return self._watcher

    def add_step_callback(self, fn) -> None:
        """Register ``fn(loss, examples, dt)`` to run after every step.

        ``loss`` is the (possibly lazy) device value — callbacks should only
        force it at publish time (see :class:`metrics.MetricsReporter`);
        ``dt`` is the wall time since the previous ``step`` call, so long-run
        examples/sec is exact without breaking async dispatch.
        """
        self._step_callbacks.append(fn)

    def step(self, batch) -> float:
        """One sharded optimizer step; returns the (replicated) loss.

        The call is the ``trainer.step`` span (children ``trainer.shard``
        and ``trainer.dispatch``, ``trainer.checkpoint`` where one is
        taken): one pair of clock reads each feeds the ring, the flight
        stages ``shard`` / ``compute``, the goodput ledger and, in a
        profiler session, an annotation of the same name and ``step``.
        The device's side of the step is ``trainer.device_step`` (attr
        ``step``, this span's) and, for a batch that was the host's,
        ``trainer.h2d``: the completion watcher (:class:`_DeviceWatcher`)
        writes both from its own threads, in the ring only, when the loss
        and the batch are ready; no step waits for either."""
        if self._watchdog is not None:
            return self._watchdogged_step(batch)
        with obs.span("trainer.step",
                      step=self._steps_done + 1).root() as sp:
            loss = self._dispatch(batch, wait=False)
            loss = self._after_step(loss, batch)
        self.last_step_trace_id = sp.trace_id or self.last_step_trace_id
        return loss

    def _dispatch(self, batch, *, wait: bool):
        """Shard the batch and call the jitted step, inside the open
        ``trainer.step`` span.  `compute` is the dispatch wall: on async
        backends it understates true device time until dispatch throttling
        backs up — which is exactly when a step becomes device-bound and
        the number grows; with ``wait`` (the watchdogged step) the loss is
        forced inside it, so it is true device wall.  The device's time is
        the ``trainer.device_step`` span, which the completion watcher
        ends when the loss is ready (its ``dispatch_s`` is this wall); the
        flight stage and the goodput ledger keep `compute` as their input.
        The shard is its own
        `shard` stage (not `stage`): a feed that already device_put the
        batch recorded the real transfer as `stage`, and this re-shard of
        device-resident arrays is ~free — sharing the name would
        bimodalize that histogram toward zero."""
        watcher = self._watch()
        with obs.span("trainer.shard") as sh:
            staged = shard_batch(self.mesh, batch, self.sequence_axes)
        with obs.span("trainer.dispatch") as run:
            self.state, loss = self.train_step(self.state, staged)
            if wait:
                import jax

                loss = jax.block_until_ready(loss)
        if watcher is not None:
            # a batch the feed staged (it passed through ``shard_batch``)
            # brought its arrival; one that was the host's until now began
            # its transfer under ``trainer.shard``
            first = _first_leaf(staged)
            arrival = (self._staged_arrivals.get(id(first))
                       if first is _first_leaf(batch)
                       else watcher.staged(batch, staged, sh.t0))
            watcher.stepped(self._steps_done + 1, run.t0, run.dur_s,
                            arrival, loss)
        # the spans' durations are the flight stages' and the goodput
        # ledger's (the first step's compute wall IS the jit compile —
        # note_step books it): no clock is read again
        self._flight.add(shard=sh.dur_s, compute=run.dur_s)
        obs.ledger.goodput().note_step(sh.dur_s, run.dur_s)
        return loss

    def _after_step(self, loss, batch):
        """Shared post-step accounting: wall-time + examples → callbacks
        and the obs registry (steps/examples counters, step-time
        histogram — the per-node series ``TFCluster.metrics()`` rolls
        up)."""
        now = time.perf_counter()
        dt = now - self._last_step_t if self._last_step_t else 0.0
        self._last_step_t = now
        n = _batch_examples(batch)
        self._steps_done += 1
        obs.counter("trainer_steps_total").inc()
        if n:
            obs.counter("trainer_examples_total").inc(n)
        if self._batch_counters is not None:
            first = _first_leaf(batch)
            counts = (self._batch_counters(batch, self.config)
                      if isinstance(first, np.ndarray)
                      else self._staged_counts.get(id(first)))
            for name, value in (counts or {}).items():
                obs.counter(name).inc(value)
        if self._device_counters is not None:
            self._device_counters.after_step(self.state.collections)
        if dt > 0:
            obs.histogram("trainer_step_seconds").observe(dt)
        # wall-clock heartbeat for the driver's stall detector
        # (obs.anomaly): a node whose gauge falls behind the freshest
        # peer is wedged — visible from the rollup without any new RPC
        obs.gauge("trainer_last_step_unix_ts").set(time.time())
        # close the feed-plane flight record (DataFeed wait/ingest + this
        # step's stage/compute) into one classified bottleneck verdict
        self._flight.commit()
        self._maybe_checkpoint()
        for cb in self._step_callbacks:
            cb(loss, n, dt)
        # elastic membership: the regroup flag is checked HERE, between
        # steps, riding the same per-step plumbing as the watchdog and
        # heartbeat — the step that just completed is fully accounted
        # (checkpoint cadence included) before the loop is interrupted
        if self._elastic is not None and self._elastic.regroup_pending():
            from tensorflowonspark_tpu import elastic as elastic_lib

            raise elastic_lib.RegroupSignal(self._elastic.command())
        return loss

    @staticmethod
    def _batch_signature(batch):
        """Hashable fingerprint of a batch's full (structure, shape, dtype)
        tree — the watchdog's warm-shape key, delegated to
        ``shapes.signature`` (the ONE compile-triggering shape policy, so
        the trainer's notion of "same compiled shape" can never drift
        from the serving planes' or the warmup enumeration's).  Leaf
        dtypes are included and non-dict batches key by their whole
        pytree (ADVICE r5: a dtype-only change with identical shapes, or
        any reshape of a non-dict batch — which the old key collapsed to
        one ``None`` — recompiles, and an armed window across that
        compile would read minutes of XLA as a wedge and ``os._exit`` a
        healthy trainer).  ``portable=False``: the watchdog key is
        in-process only, so it keys on the treedef OBJECT — type-exact
        even for same-named custom pytree nodes."""
        from tensorflowonspark_tpu import shapes

        return shapes.signature(batch, portable=False)

    def _watchdogged_step(self, batch) -> float:
        """step() under the mid-run wedge watchdog: the loss is forced to
        the host inside the armed window, so a wedged chip trips the
        watchdog instead of deferring the hang to a later fetch.

        The watchdog only arms for batch signatures it has already seen
        complete once: jit compiles lazily on first call (and recompiles on
        a shape OR dtype change, e.g. a short final batch), and minutes of
        XLA compilation inside an armed window would read as a wedge and
        kill a healthy trainer.  Unarmed steps still hang forever on a
        truly wedged chip — but the first step of a run meeting a wedged
        chip is the rendezvous health probe's job
        (health.probe_chip_health), not this watchdog's.
        """
        signature = self._batch_signature(batch)
        armed = signature in self._watchdog_warm_shapes
        with obs.span("trainer.step",
                      step=self._steps_done + 1).root() as sp:
            if armed:
                self._watchdog.arm()
            try:
                loss = self._dispatch(batch, wait=True)
            finally:
                # disarm on ANY exit: an exception a caller handles must
                # not leave a stale armed timestamp that later reads as a
                # stall
                self._watchdog.beat()
            self._watchdog_warm_shapes.add(signature)
            loss = self._after_step(loss, batch)
        self.last_step_trace_id = sp.trace_id or self.last_step_trace_id
        return loss

    def predict(self, batch):
        # staged here, not through ``shard``: a batch that no step takes
        # has nothing to count and no ``trainer.h2d`` to pair with one
        staged = shard_batch(self.mesh, batch, self.sequence_axes)
        if getattr(self.forward_fn, "stateful", False):
            return self.eval_step(self.state.params, self.state.collections,
                                  staged)
        return self.eval_step(self.state.params, staged)

    @property
    def params(self):
        return self.state.params

    # -- checkpointing -------------------------------------------------------

    def _state_tree(self) -> dict:
        tree = {"params": self.state.params,
                "opt_state": self.state.opt_state,
                "step": self.state.step}
        if self.state.collections:
            tree["collections"] = self.state.collections
        return tree

    def save(self, path: str) -> None:
        from tensorflowonspark_tpu import ckpt

        ckpt.save_pytree(self._state_tree(), path)

    def checkpoint(self, directory: str, every_steps: int | None = None,
                   max_to_keep: int = 3, async_save: bool = True):
        """Enable periodic step-numbered checkpoints (preemption tolerance).

        Every ``every_steps`` completed steps (default: the
        ``TFOS_CKPT_EVERY_STEPS`` env, 0 = manual-only), the full train
        state is saved through a :class:`ckpt.CheckpointManager` — async
        by default, so the write happens OFF the step path (the step pays
        one device→host snapshot; orbax finalises in the background and
        ``latest_step`` only ever names committed checkpoints, so a crash
        mid-write costs nothing).  The cadence bounds lost work on
        executor loss: the elastic regroup restores survivors from the
        last committed step (:meth:`restore_latest`).  Returns the
        manager (also used for manual ``save``/``restore``)."""
        from tensorflowonspark_tpu import ckpt

        if every_steps is None:
            env = os.environ.get("TFOS_CKPT_EVERY_STEPS", "")
            every_steps = int(env) if env else 0
        self._ckpt_every = max(0, int(every_steps))
        self._ckpt_mgr = ckpt.CheckpointManager(
            directory, max_to_keep=max_to_keep, async_save=async_save)
        return self._ckpt_mgr

    def _maybe_checkpoint(self) -> None:
        if self._ckpt_mgr is None or self._ckpt_every <= 0:
            return
        if self._steps_done % self._ckpt_every:
            return
        import numpy as np

        # forcing state.step syncs the device — but only on the save
        # cadence, where the save itself snapshots the same state anyway
        step = int(np.asarray(self.state.step))
        with obs.span("trainer.checkpoint", step=step) as sp:
            self._ckpt_mgr.save(step, self._state_tree())
        # async saves return after the device→host snapshot; that
        # snapshot wall is the step path's real checkpoint cost, which
        # is exactly what the goodput breakdown should book
        obs.ledger.goodput().note_checkpoint(sp.dur_s)
        self.last_checkpoint_step = step

    def restore_latest(self) -> int | None:
        """Restore the newest committed periodic checkpoint into this
        trainer; returns its step, or None when there is none yet.

        The restore targets THIS trainer's (possibly re-built, possibly
        differently-meshed) state template, so the checkpoint is resharded
        to the reader's topology — the elastic-regroup path rebuilds the
        mesh over the survivors and restores straight into it."""
        if self._ckpt_mgr is None:
            raise RuntimeError("checkpoint() was never enabled")
        step = self._ckpt_mgr.latest_step()
        if step is None:
            return None
        self._take_state(
            self._ckpt_mgr.saved_tree(step),
            lambda target: self._ckpt_mgr.restore(step, target=target))
        return int(step)

    def finish_checkpoints(self) -> None:
        """Barrier on in-flight async checkpoint writes (shutdown/rejoin:
        the last snapshot must commit before this process lets go)."""
        if self._ckpt_mgr is not None:
            self._ckpt_mgr.wait_until_finished()

    def attach_elastic(self, worker) -> None:
        """Ride the step loop's between-steps plumbing with an elastic
        regroup check: once ``worker.regroup_pending()``, the NEXT
        completed step raises :class:`elastic.RegroupSignal` (after its
        metrics, checkpoint cadence, and callbacks ran), so the training
        loop can tear down and rejoin at a step boundary."""
        self._elastic = worker

    def export(self, export_dir: str, *, self_describing: bool = True) -> str:
        """Write a serving export: weights + serialized forward + signature.

        The SavedModel-parity artifact (``saved_model.py``): consumers
        (``TFModel.transform``, the JNI shim) serve it with no model code.
        Optimizer state and optimizer-only collections (the sparse embedding
        engine's per-row accumulators, suffix ``_opt``) are stripped — they
        are dead weight at serving time.  ``self_describing=False`` keeps
        the round-1-3 weights-only layout.
        """
        from tensorflowonspark_tpu import compat, saved_model

        # hand orbax the (possibly sharded, possibly not-fully-addressable)
        # jax.Arrays directly — it gathers during serialization; a host
        # np.asarray here would break multi-host ZeRO exports and double
        # host RAM on single host
        state: dict[str, Any] = {"params": self.state.params}
        serving_cols = {k: v for k, v in self.state.collections.items()
                        if not k.endswith("_opt")}
        if serving_cols:
            state["collections"] = serving_cols
        if not self_describing:
            return compat.export_saved_model(state, export_dir)
        label_keys = {"label", "start_positions", "end_positions"}
        example = {
            k: np.asarray(v)
            for k, v in self.module_lib.example_batch(
                self.config, batch_size=2).items()
            if k not in label_keys
        }
        # Serialize a MESH-FREE rebuild of the forward, not self.forward_fn:
        # the training model may close over the mesh (ring attention under
        # sp>1, the GPipe shard_map under pp>1) and jax.export of those
        # collective paths hangs/fails — and serving is single-device
        # semantics anyway.  Params are layout-identical across the two
        # builds (same module, mesh only changes execution strategy).
        serve_model = self.module_lib.make_model(self.config)
        serve_forward = self.module_lib.make_forward_fn(
            serve_model, self.config)
        return compat.export_saved_model(
            state, export_dir,
            forward_fn=saved_model.wrap_state_forward(serve_forward),
            example_batch=example, model_name=self.model_name)

    def restore(self, path: str) -> None:
        from tensorflowonspark_tpu import ckpt

        self._take_state(ckpt.saved_tree(path),
                         lambda target: ckpt.load_pytree(path, target))

    def _take_state(self, saved, load) -> None:
        """Make ``load(target)`` of a checkpoint whose tree is ``saved``
        this trainer's state.  The target is the trainer's own tree, so
        whatever the checkpoint lacks of it is refused by the leaf's name —
        but for the rows of a collection that only the counters read (the
        model module's ``counter_rows(config) -> {collection: rows}``): a
        checkpoint written before such a row was counted restores with the
        row at zero, and resumes."""
        target = self._state_tree()
        zeros = {}
        rows_of = getattr(self.module_lib, "counter_rows", None)
        for name, rows in (rows_of(self.config) if rows_of else {}).items():
            live = target.get("collections", {}).get(name, {})
            have = saved.get("collections", {}).get(name, {})
            zeros[name] = {row: live[row] * 0 for row in rows
                           if row in live and row not in have}
            if zeros[name]:
                target["collections"] = {
                    **target["collections"],
                    name: {row: v for row, v in live.items()
                           if row not in zeros[name]}}
        restored = load(target)
        collections = restored.get("collections", {})
        for name, rows in zeros.items():
            if rows:
                collections = {**collections,
                               name: {**collections[name], **rows}}
        self.state = TrainState(restored["params"], restored["opt_state"],
                                restored["step"], collections)
        if self._device_counters is not None:
            self._device_counters.rebase(self.state.collections)


class _DeviceCounters:
    """Counters of what the device decided, from the step's collections.

    A model module's ``device_counters(collections, config)`` names
    cumulative integer arrays (any shape) inside the collections.  After
    every step a small jitted copy of them is dispatched behind the step
    and its transfer to the host started; the step after reads it (it is
    there by then: no step waits for anything but its own loss) and adds
    each array's growth, element by element and modulo 2**32, to the
    ``obs`` counter of its name.  The copy is compiled when the Trainer is
    built, and then again never.  :meth:`drain` reads what is pending (the
    last step's, when the Trainer goes); :meth:`rebase` takes restored
    collections as the new zero."""

    def __init__(self, make_counts, config, collections):
        import jax

        self._copy = jax.jit(lambda cols: jax.tree_util.tree_map(
            lambda v: v + 0, make_counts(cols, config)))
        self._pending = None
        self.rebase(collections)

    def rebase(self, collections) -> None:
        self.drain()
        self._seen = {name: np.asarray(value).astype(np.int64)
                      for name, value in self._copy(collections).items()}

    def after_step(self, collections) -> None:
        self.drain()
        self._pending = self._copy(collections)
        for value in self._pending.values():
            value.copy_to_host_async()

    def drain(self) -> None:
        pending, self._pending = self._pending, None
        for name, value in (pending or {}).items():
            now = np.asarray(value).astype(np.int64)
            obs.counter(name).inc(int(((now - self._seen[name])
                                       % (1 << 32)).sum()))
            self._seen[name] = now


class _Arrival:
    """When a staged batch was whole on the device: ``t0`` the staging
    call's start, ``t1`` the watcher's clock read once every staged array
    was ready (None while it is not, or if the transfer failed); both on
    ``time.time()``."""

    __slots__ = ("t0", "t1", "done")

    def __init__(self, t0: float):
        self.t0 = t0
        self.t1: float | None = None
        self.done = threading.Event()


class _DeviceWatcher:
    """The device's side of every step, seen from the host: two daemon
    threads that wait where no training thread may (``block_until_ready``
    releases the GIL), read ``time.time()`` and write ring spans.

    - ``trainer.h2d``, one a staged batch: from the staging call's start
      to every staged array being ready on the device; attr ``bytes`` (the
      host arrays' ``nbytes``).  A batch whose arrays were all the
      device's already has none.
    - ``trainer.device_step``, one a step (attr ``step``): it ends when the
      step's loss is ready and begins at the latest of the previous step's
      end, its ``trainer.dispatch`` start and its batch's arrival
      (``after``: ``prev`` / ``dispatch`` / ``input``).  ``input_wait_s``
      is by how much the arrival followed the dispatch's start,
      ``dispatch_s`` the dispatch's wall.  **An upper estimate of the
      device's busy time, not the device's own clock**: where the
      dispatch's start began it, the device's first operation lies
      somewhere inside ``dispatch_s`` or shortly after (the host cannot
      know where), and the end includes this thread's wake-up.

    A transfer and a step finish in either order, and nothing waits for
    "whichever first": so one thread a kind, each its own queue, one
    ``put`` a batch and one a step.  A transfer's arrays are held until
    they are ready, so a wait that never ends (a wedged device) keeps
    alive behind it what the feed goes on to stage — its prefetch depth,
    and never more than ``MAX_PENDING`` batches; the state is never
    touched (it is donated).  A queue that deep takes no more: that batch
    or step goes without its span.
    """

    MAX_PENDING = 64

    def __init__(self):
        self._transfers: queue.SimpleQueue = queue.SimpleQueue()
        self._steps: queue.SimpleQueue = queue.SimpleQueue()
        self._threads = [
            threading.Thread(target=fn, args=(inbox,), daemon=True, name=name)
            for fn, inbox, name in (
                (_watch_transfers, self._transfers, "tfos-trainer-h2d"),
                (_watch_steps, self._steps, "tfos-trainer-device-step"))]
        for thread in self._threads:
            thread.start()

    def staged(self, batch, staged, t0: float):
        """Hand over what one ``shard_batch`` call moved; returns the
        batch's :class:`_Arrival`, or None where every array passed
        through (or the queue is full)."""
        import jax

        moved, nbytes = [], 0
        for host, dev in zip(jax.tree_util.tree_leaves(batch),
                             jax.tree_util.tree_leaves(staged)):
            if dev is not host:
                moved.append(dev)
                nbytes += int(getattr(host, "nbytes", 0))
        if not moved or self._transfers.qsize() >= self.MAX_PENDING:
            return None
        arrival = _Arrival(t0)
        self._transfers.put((arrival, moved, nbytes))
        return arrival

    def stepped(self, step: int, dispatch_t0: float, dispatch_s: float,
                arrival, loss) -> None:
        if self._steps.qsize() < self.MAX_PENDING:
            self._steps.put((step, dispatch_t0, dispatch_s, arrival, loss))

    def close(self, timeout_s: float = 5.0) -> None:
        """Let both threads finish what they hold, then end them (the
        collector may run this on one of them: that one is not joined)."""
        for inbox in (self._transfers, self._steps):
            inbox.put(None)
        for thread in self._threads:
            if thread is not threading.current_thread():
                thread.join(timeout_s)


def _watch_transfers(inbox) -> None:
    import jax

    while True:
        item = inbox.get()
        if item is None:
            return
        arrival, moved, nbytes = item
        try:
            jax.block_until_ready(moved)
            arrival.t1 = time.time()
        except Exception:       # the step that takes the batch raises it
            logger.debug("a staged batch never became ready", exc_info=True)
        # the arrays are let go before the next wait, whatever came of them
        item = moved = None
        arrival.done.set()
        if arrival.t1 is not None:
            obs.complete("trainer.h2d", arrival.t0, arrival.t1 - arrival.t0,
                         bytes=nbytes)


def _watch_steps(inbox) -> None:
    import jax

    prev_end = 0.0
    while True:
        item = inbox.get()
        if item is None:
            return
        step, dispatch_t0, dispatch_s, arrival, loss = item
        try:
            jax.block_until_ready(loss)
        except Exception:       # the caller meets it when it reads the loss
            logger.debug("step %d's loss never became ready", step,
                         exc_info=True)
            item = loss = None
            continue
        end = time.time()
        item = loss = None
        arrived = 0.0
        if arrival is not None:
            # the loss is ready, so the batch was: at most the other
            # thread's wake-up is waited for
            arrival.done.wait(1.0)
            arrived = arrival.t1 or 0.0
        begin, after = max((prev_end, "prev"), (dispatch_t0, "dispatch"),
                           (arrived, "input"))
        begin = min(begin, end)
        obs.complete("trainer.device_step", begin, end - begin, step=step,
                     after=after, dispatch_s=dispatch_s,
                     input_wait_s=max(0.0, arrived - dispatch_t0))
        prev_end = end


def _keep_under(table: dict, leaf, value) -> None:
    """``table[id(leaf)] = value`` for as long as ``leaf`` (a staged
    batch's first array) lives: what a feed's staging call knows waits
    there for the step that is handed the batch, and goes with the array
    if none is."""
    table[id(leaf)] = value
    weakref.finalize(leaf, table.pop, id(leaf), None)


def _first_leaf(batch):
    """The batch's first array: NumPy's while the batch is the host's, a
    ``jax.Array`` once it is staged; None for an empty batch."""
    import jax

    return next(iter(jax.tree_util.tree_leaves(batch)), None)


def _batch_examples(batch) -> int:
    """Leading-dim size of the first array leaf (examples in the batch)."""
    import jax

    for leaf in jax.tree_util.tree_leaves(batch):
        shape = getattr(leaf, "shape", None)
        if shape:
            return int(shape[0])
    return 0
