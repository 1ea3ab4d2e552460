"""What runs in the trainer process: the benchmark's ``map_fun``.

One object — the Trainer with its compiled step and its state — is built,
loaded with the seeded weights, driven through its first three steps by the
cell's own feed (the output check reads them), warmed up, and handed to the
measured window.  After the window the feed is ended, the device's counters
are read, the program's state is freed and the reference follows the same
three steps.  The report goes to ``plan["out_dir"]/trainer_report.json``;
the launcher turns it into metrics.
"""

from __future__ import annotations

import json
import os
import time
import traceback

CHECK_STEPS = 3
REPORT = "trainer_report.json"


def map_fun(plan, ctx) -> None:
    report = {"t_map_fun": time.time(), "pid": os.getpid()}
    if not isinstance(plan, dict):      # TFEstimator hands on a Namespace
        plan = vars(plan)
    try:
        _run(plan, ctx, report)
    except BaseException:
        report["error"] = traceback.format_exc()
        raise
    finally:
        report["t_return"] = time.time()
        path = os.path.join(plan["out_dir"], REPORT)
        with open(path + ".tmp", "w", encoding="utf-8") as f:
            json.dump(report, f)
        os.replace(path + ".tmp", path)


def _device_report(jax) -> dict:
    devices = jax.local_devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "local_count": len(devices)}


def _memory_peak(jax) -> dict:
    """Peak bytes on the fullest chip.  The TPU runtime reports the live
    buffers (``peak_bytes_in_use``) and the running program's scratch
    (``peak_bytes_reserved``) apart; a step holds both at once, so the
    chip's peak is their sum."""
    best = {"memory_peak_bytes": 0}
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        in_use = int(stats.get("peak_bytes_in_use", 0))
        reserved = int(stats.get("peak_bytes_reserved", 0))
        if in_use + reserved >= best["memory_peak_bytes"]:
            best = {"memory_peak_bytes": in_use + reserved,
                    "peak_bytes_in_use": in_use,
                    "peak_bytes_reserved": reserved,
                    "bytes_limit": int(stats.get("bytes_limit", 0))}
    return best


class _CompileCounter:
    """Counts executables jit had to get (compiled or loaded from the
    persistent cache): none may be needed inside the window."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self, jax):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event in self.EVENTS:
            self.count += 1


def change_norms(jax, program, reference, trainer, config, seed, names):
    """Per-leaf norm of (current parameters - seeded parameters).  The
    seeded leaves are made again from the seed — a leaf at a time where the
    reference can (``make_leaf``: a model of large tables) — so no copy of
    the first parameters is kept on the device beside the program's state."""
    import jax.numpy as jnp

    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b))))
    make_leaf = getattr(reference, "make_leaf", None)
    everything = None if make_leaf else reference.make_weights(config, seed)
    out = {}
    for name in names:
        first = (make_leaf(config, seed, name) if make_leaf
                 else everything[name])
        now = program.parameters(trainer, config, [name])[name]
        out[name] = float(diff(now, first))
        del first, now
    return out


def _run(plan, ctx, report) -> None:
    from tensorflowonspark_tpu import util

    util.ensure_jax_platform()
    t_import = time.time()
    import jax

    from benchmark import check, peaks, spec, trace_reduce

    report["device"] = device = _device_report(jax)
    if plan["require_chip"]:
        if device["platform"] != "tpu" or device["local_count"] < plan["chips"]:
            raise peaks.NoAcceleratorError(
                f"the cell asks for {plan['chips']} TPU chip(s); the trainer "
                f"found {device['local_count']} x {device['platform']} "
                f"({device['kind']}): no accelerator, no run")
        peaks.peaks_for(device["kind"])
    compiles = _CompileCounter(jax)
    config, traffic, seed = plan["config"], plan["traffic"], plan["seed"]
    program = spec.module(plan["config_package"], "program")
    reference = spec.module(plan["config_package"], "reference")
    feed_mod = spec.module(plan["package"], "feeds", traffic["feed"])
    generator = spec.module(plan["package"], "traffic", traffic["generator"])

    trainer = program.build(config, ctx)
    names = program.load_weights(trainer, config, reference, seed)
    report["t_trainer_built"] = time.time()
    feed = feed_mod.open_feed(plan, ctx, program, trainer, plan["batch"])

    def one_step(item):
        return float(jax.block_until_ready(trainer.step(item.batch)))

    # -- the first three steps, through the window's own feed and call ------
    mine = {"losses": [], "ids": []}
    for i in range(CHECK_STEPS):
        item = feed.next()
        if item is None:
            raise RuntimeError("the feed ended during the first steps")
        mine["losses"].append(one_step(item))
        mine["ids"].append([int(v) for v in item.ids])
        if i == 0:
            report["t_first_step_done"] = time.time()
            mine["grad_norms"] = program.first_gradient_norms(
                trainer, config, names)
    mine["change_norms"] = change_norms(jax, program, reference, trainer,
                                         config, seed, names)
    report["trainer_ready_s"] = report["t_first_step_done"] - t_import
    for _ in range(traffic["warmup_steps"]):
        item = feed.next()
        if item is None:
            raise RuntimeError("the feed ended during warm-up")
        one_step(item)
    from tensorflowonspark_tpu import compile_cache

    report["cache"] = {k: compile_cache.stats().get(k)
                       for k in ("dir", "disk_hits", "error")}

    # -- the window ---------------------------------------------------------
    seconds = float(plan["seconds"])
    trace_on = bool(plan["trace"])
    trace_first = traffic["trace_after_steps"]
    trace_last = trace_first + traffic["trace_steps"]
    trace_dir = os.path.join(plan["out_dir"], "trace")
    tracing, traced = None, None
    waits, steps, ends, losses, rows, nbytes = [], [], [], [], [], []
    compiles_before = compiles.count
    report["t_window_start"] = time.time()
    t0 = time.perf_counter()
    n = 0
    while True:
        if trace_on and n == trace_first:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            tracing = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
            tracing.__enter__()
        a = time.perf_counter()
        with jax.profiler.TraceAnnotation("feed_wait"):
            item = feed.next()
        b = time.perf_counter()
        if item is None:
            raise RuntimeError(
                f"the feed ended {b - t0:.1f}s into a {seconds:.0f}s window:"
                " the traffic file's epochs do not cover the window")
        with jax.profiler.TraceAnnotation("step_dispatch"):
            loss = one_step(item)
        c = time.perf_counter()
        n += 1
        if tracing is not None and n == trace_last:
            tracing.__exit__(None, None, None)
            tracing = None
            jax.profiler.stop_trace()
            traced = True
        if c - t0 > seconds:
            break       # this step did not complete inside the window
        waits.append(b - a)
        steps.append(c - b)
        ends.append(c - t0)
        losses.append(loss)
        rows.append(item.rows)
        nbytes.append(item.nbytes)
    if tracing is not None:
        tracing.__exit__(None, None, None)
        jax.profiler.stop_trace()
        traced = True
    report["t_window_end"] = time.time()
    report["window"] = {
        "seconds": seconds, "feed_wait_s": waits, "step_s": steps,
        "step_end_s": ends, "losses": losses, "rows": sum(rows),
        "bytes": sum(nbytes), "steps": len(ends),
        "compilations": compiles.count - compiles_before}

    # -- end the feed, read the device, free the program --------------------
    report["feed"] = feed.end()
    report["memory"] = _memory_peak(jax)
    del trainer, feed, item
    import gc

    gc.collect()
    if traced:
        import glob

        found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                                 recursive=True))
        try:
            report["trace"] = trace_reduce.reduce_file(found[-1])
            report["trace"]["file"] = found[-1]
        except ValueError as e:
            # a rehearsal on the CPU has no device plane; on the chip a
            # traced run in which nothing ran on the device is a failure
            if plan["require_chip"]:
                raise
            report["trace_error"] = str(e)

    # -- the output check: the reference follows the same three steps -------
    t_check = time.time()
    batches = [generator.rows(traffic, seed, ids) for ids in mine["ids"]]
    theirs = reference.follow(config, seed, batches)
    report["check"] = {"program": mine, "reference": theirs,
                       "numbers": check.numbers(mine, theirs),
                       "seconds": time.time() - t_check}
    report["t_done"] = time.time()
