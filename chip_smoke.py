"""Standing proof that the system still starts on the chip.

Drives the main path — ``TFCluster.run`` → ``sparkapi`` executor → chip
claim → rendezvous health probe → trainer process → feed plane →
``Trainer.step`` — through the entry points a user calls, on a TPU, and
checks what comes out.  It measures nothing that is gated: the times it
prints are for the builder's notes.

    python chip_smoke.py            # one chip: resnet, feed, warm-start phases
    python chip_smoke.py --chips 4  # ONLY the four-chip mesh phase and the
                                    # one-chip run it is compared with

This process is a launcher and a checker: it never imports JAX (a parent
that has touched JAX holds the chip, and the child that needs it then fails
or hangs).  Every phase runs in a fresh driver process of its own — as a
user's ``python my_job.py`` would — whose cluster starts the executor, the
health-probe child and the trainer exactly as the framework does; the
driver's last stdout line is a JSON report, which this process checks.  A
phase that fails ends the run at once, non-zero, with the phase's name and
its captured stderr.  There is no fallback: a child that reports a platform
other than ``tpu`` stops the run before any phase starts.

The last line of stdout is the contract's
``{"ok": true, "device": {"platform", "kind", "count"}}``, the device as
the trainer's JAX reported it.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import signal
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

#: the contract's limit is 1200 s; stop starting work well before it
WALL_BUDGET_S = 1100.0

RESNET50_PARAMS = 25_557_032  # default GroupNorm ResNet-50: width AND depth

#: Phase 1 — ResNet-50 at every published width and full depth, batch 128,
#: ImageNet-shaped TFRecords through the readers.  256 seeded records read
#: for 8 epochs: 2 warm-up + 14 timed steps over the same shard, so the loss
#: must fall.
RESNET_PLAN = {
    "stage_sizes": [3, 4, 6, 3], "params": RESNET50_PARAMS, "batch": 128,
    "records": 256, "parts": 2, "epochs": 8, "warmup": 2, "lr": 1e-3,
    "chips": 1, "platform": "tpu", "timeout_s": 600,
}
#: Phase 3 — the same trainer in a fresh process on the same cache: two steps
WARM_PLAN = dict(RESNET_PLAN, epochs=1, warmup=1, timeout_s=300)
#: Phase 2 — acceptance config 1: MNIST-width rows through the Spark feed
FEED_PLAN = {
    "rows": 4096, "epochs": 2, "batch": 128, "chips": 1, "platform": "tpu",
    "timeout_s": 300,
}
#: --chips 4 — six steps of full-width ResNet-50 at GLOBAL batch 128 on two
#: seeded device-resident batches; leg one on a 4-chip mesh (32 per chip),
#: leg two on one chip.  The optimizer is SGD with momentum, not Trainer's
#: default AdamW: Adam's first updates are lr*sign(g), so a last-bit
#: difference in a near-zero gradient moves a weight by a whole step, and
#: the two legs — right to 2.1e-5 on the first loss — were 1.8e-2 apart by
#: step 3 (v5e, PR 21).  SGD is linear in the gradient, has a param-shaped
#: state for the sharded update to shard, and shows a wrong exchange (a sum
#: for a mean) that Adam's normalisation would hide.
MESH_PLAN = {
    "stage_sizes": [3, 4, 6, 3], "params": RESNET50_PARAMS, "batch": 128,
    "steps": 6, "lr": 0.01, "momentum": 0.9, "platform": "tpu",
    "timeout_s": 500,
}
#: Loss agreement between the four-chip and the one-chip trajectory.
#: GroupNorm keeps no cross-example statistics, so the two differ only in
#: reduction order and in what the compiler does at 32 rows a chip against
#: 128.  The float32 CPU mesh-equivalence tests hold 5e-5; activations
#: here are bfloat16.  All figures: v5e, PR 21.
#: The FIRST loss is the forward pass at the same seeded weights.  Two
#: differently compiled programs disagree on it by 2.1e-5, 2.5e-5 and
#: 3.7e-5 (three pairs: four chips against one under Adam and under SGD,
#: and Adam's one-chip program against SGD's), so 5e-5 would hold only
#: until the next change to the step; the bound is three times the largest.
#: After the first update the weights differ in their last bits, and
#: bfloat16 activations turn any such difference into 2**-9 rounding
#: flips: on one chip the SAME program fed each batch rotated by 32 rows —
#: nothing but another summation order — agrees to 1.2e-7 and 4.5e-7 on
#: the first two losses and is 7.0e-4 off by the sixth (the same run
#: repeated is bit-identical).  Four chips against one came to 7.6e-4.  The
#: bound for the whole trajectory is seven times that measured floor.
MESH_FIRST_LOSS_RTOL = 1e-4
MESH_LOSS_RTOL = 5e-3


# ---------------------------------------------------------------------------
# map_funs: what runs in the trainer process, through Trainer and the feeds
# ---------------------------------------------------------------------------


def _device_report() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "backend": jax.default_backend(),
            "kind": devices[0].device_kind, "count": len(devices),
            "local_count": len(jax.local_devices())}


def _param_count(trainer) -> int:
    import jax

    return sum(int(leaf.size)
               for leaf in jax.tree_util.tree_leaves(trainer.params))


def _cache_report() -> dict:
    from tensorflowonspark_tpu import compile_cache

    st = compile_cache.stats()
    return {"dir": st["dir"], "disk_hits": st["disk_hits"],
            "disk_writes": st["disk_writes"], "error": st["error"]}


def resnet_map_fun(plan, ctx):
    """``examples/imagenet/resnet_spark.py``'s map_fun — Trainer, TFRecord
    readers staging onto the mesh, ``trainer.step`` — with the full config
    passed explicitly and every step's loss and wall time kept."""
    import shutil

    from tensorflowonspark_tpu import util

    util.ensure_jax_platform()
    import jax

    from tensorflowonspark_tpu import readers
    from tensorflowonspark_tpu.models import resnet
    from tensorflowonspark_tpu.native import tfrecord_native
    from tensorflowonspark_tpu.trainer import Trainer

    codec = "native" if tfrecord_native.available() else "python"
    if codec != "native" and shutil.which("g++"):
        raise RuntimeError("g++ is present but the native TFRecord codec "
                           f"did not load: {tfrecord_native.load_error()}")

    config = resnet.Config(stage_sizes=tuple(plan["stage_sizes"]))
    t0 = time.perf_counter()
    trainer = Trainer("resnet50", config=config, learning_rate=plan["lr"],
                      error_sink=ctx.report_error)
    init_s = time.perf_counter() - t0

    shard = readers.shard_files(os.path.join(plan["data_dir"], "part-*"),
                                ctx.executor_id, ctx.num_workers)
    losses, step_s, iter_s = [], [], []
    t_prev = time.perf_counter()
    for batch in readers.tfrecord_batches(
            shard, plan["batch"],
            parse_fn=resnet.tfrecord_parse_fn(config.image_size),
            num_epochs=plan["epochs"], readers=2, drop_remainder=True,
            prefetch=2, device_put=trainer.shard):
        t1 = time.perf_counter()
        losses.append(float(jax.block_until_ready(trainer.step(batch))))
        t2 = time.perf_counter()
        step_s.append(t2 - t1)      # in step(): dispatch → loss on the host
        iter_s.append(t2 - t_prev)  # whole iteration: feed wait + step
        t_prev = t2

    if not losses:
        raise RuntimeError(f"the readers yielded no batch from {shard}")
    warm = plan["warmup"]
    ctx.mgr.set("chip_smoke", {
        "device": _device_report(), "params": _param_count(trainer),
        "codec": codec,
        "batch": plan["batch"], "steps": len(losses), "losses": losses,
        "step_s": [round(t, 4) for t in step_s],
        "trainer_init_s": init_s, "first_step_s": step_s[0],
        "steady_step_s": statistics.median(step_s[warm:]),
        "steady_iter_s": statistics.median(iter_s[warm:]),
        "cache": _cache_report(),
    })


def feed_map_fun(plan, ctx):
    """``examples/mnist/mnist_spark.py``'s loop — ``DataFeed.next_batch`` →
    ``device_put`` → step — in the spawned trainer, with ``mnist_mlp`` at
    its only width under ``Trainer``."""
    from tensorflowonspark_tpu import util

    util.ensure_jax_platform()
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import obs
    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.trainer import Trainer

    trainer = Trainer("mnist_mlp", config=mnist.Config(),
                      error_sink=ctx.report_error)
    feed = ctx.get_data_feed(train_mode=True,
                             input_mapping=["image", "label"], prefetch=2)
    rows, losses = 0, []
    while not feed.should_stop():
        batch = feed.next_batch(plan["batch"], device_put=True)
        if not batch or batch["image"].shape[0] == 0:
            continue
        x = batch["image"].astype("float32") / 255.0
        y = batch["label"].astype("int32")
        n = x.shape[0]
        if n < plan["batch"]:  # static-shape guard, as the example pads
            x = jnp.pad(x, ((0, plan["batch"] - n), (0, 0)))
            y = jnp.pad(y, (0, plan["batch"] - n))
        losses.append(trainer.step({"image": x, "label": y}))
        rows += n
    losses = [float(v) for v in jax.device_get(losses)]
    ctx.mgr.set("chip_smoke", {
        "device": _device_report(), "rows": rows, "steps": len(losses),
        "first_loss": losses[0], "final_loss": losses[-1],
        "shm_bytes": obs.counter("datafeed_bytes_shm_total").value,
        "pickle_bytes": obs.counter("datafeed_bytes_pickle_total").value,
        "cache": _cache_report(),
    })


def mesh_map_fun(plan, ctx):
    """Full-width ResNet-50 under ``Trainer`` over every claimed chip, on
    seeded device-resident batches; proves from inside the trainer where
    the state actually lives."""
    from tensorflowonspark_tpu import util

    util.ensure_jax_platform()
    import jax
    import optax

    from tensorflowonspark_tpu.models import resnet
    from tensorflowonspark_tpu.parallel.train import (path_keys,
                                                      state_shardings)
    from tensorflowonspark_tpu.trainer import Trainer

    config = resnet.Config(stage_sizes=tuple(plan["stage_sizes"]))
    trainer = Trainer(
        "resnet50", config=config, error_sink=ctx.report_error,
        optimizer=optax.sgd(plan["lr"], momentum=plan["momentum"]))
    step = trainer.train_step
    mesh_ids = sorted(d.id for d in trainer.mesh.devices.flat)

    # the layout the step was compiled for: what state_shardings says
    want = state_shardings(trainer.state, trainer.param_shardings,
                           trainer.mesh)
    want_opt = getattr(step, "opt_state_shardings", None) or want.opt_state

    def placement(tree, shardings):
        """path → (bytes on one device, sharded?) for every leaf, raising
        on a leaf that is not laid out over the whole mesh as compiled."""
        out = {}

        def one(path, leaf, sharding):
            name = "/".join(path_keys(path))
            on = sorted(d.id for d in leaf.sharding.device_set)
            if on != mesh_ids:
                raise RuntimeError(f"{name} lives on devices {on}, the "
                                   f"mesh is {mesh_ids}")
            if not leaf.sharding.is_equivalent_to(sharding, leaf.ndim):
                raise RuntimeError(f"{name}: sharding {leaf.sharding} is "
                                   f"not the compiled {sharding}")
            sharded = any(axis is not None for axis in sharding.spec)
            out[name] = (leaf.addressable_shards[0].data.nbytes, sharded)

        jax.tree_util.tree_map_with_path(one, tree, shardings)
        return out

    def state_placement():
        return {"params": placement(trainer.state.params,
                                    trainer.param_shardings),
                "opt_state": placement(trainer.state.opt_state, want_opt)}

    before = state_placement()
    batches = [trainer.shard(resnet.example_batch(
        config, batch_size=plan["batch"], seed=plan["seed"] + i))
        for i in range(2)]
    # what the program asks for (lowered StableHLO) and what the TPU
    # compiler makes of it (compiled HLO); the compile lands in the
    # persistent cache, so the first trainer.step() below loads it
    lowered = step.lower(trainer.state, batches[0])
    asked, made = lowered.as_text(), lowered.compile().as_text()
    collectives = {
        "asked": {op: asked.count(f"stablehlo.{op}") for op in
                  ("reduce_scatter", "all_gather", "all_reduce")},
        "compiled": {op: made.count(f"{op}(") + made.count(f"{op}-start(")
                     for op in ("reduce-scatter", "all-gather",
                                "all-reduce")}}
    losses = [float(jax.block_until_ready(trainer.step(batches[i % 2])))
              for i in range(plan["steps"])]
    after = state_placement()
    if after != before:
        raise RuntimeError("state placement changed across steps")

    ctx.mgr.set("chip_smoke", {
        "device": _device_report(), "params": _param_count(trainer),
        "mesh": dict(trainer.mesh.shape), "batch": plan["batch"],
        "losses": losses, "collectives": collectives,
        "update_sharded": bool(getattr(step, "update_sharded", False)),
        "n_scatter_buckets": getattr(step, "n_scatter_buckets", 0),
        "opt_state_bytes": after["opt_state"],
        "n_param_leaves": len(after["params"]),
        "memory_stats": {
            str(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()},
        "cache": _cache_report(),
    })


MAP_FUNS = {"resnet": resnet_map_fun, "warm": resnet_map_fun,
            "feed": feed_map_fun, "mesh4": mesh_map_fun,
            "mesh1": mesh_map_fun}


# ---------------------------------------------------------------------------
# Phase driver: one fresh process per phase, the user's driver program
# ---------------------------------------------------------------------------


def drive_phase(name: str, plan: dict) -> dict:
    """Run one phase's cluster in THIS process (the phase's driver; no JAX
    here either) and return the trainer's report plus what the driver saw."""
    from tensorflowonspark_tpu import TFCluster, TFManager
    from tensorflowonspark_tpu.sparkapi import LocalSparkContext

    spark_mode = name == "feed"
    sc = LocalSparkContext("local-cluster[1,1,1024]", f"chip-smoke-{name}")
    cluster = TFCluster.run(
        sc, MAP_FUNS[name], plan, num_executors=1,
        input_mode=(TFCluster.InputMode.SPARK if spark_mode
                    else TFCluster.InputMode.TENSORFLOW),
        num_chips_per_executor=plan["chips"], master_node="chief")
    report: dict = {}
    if spark_mode:
        from examples.mnist.mnist_spark import synth_mnist

        x, y = synth_mnist(plan["rows"], seed=plan["seed"])
        rows = [(x[i], int(y[i])) for i in range(len(y))]
        cluster.train(sc.parallelize(rows, 1), num_epochs=plan["epochs"])
        report["rows_fed"] = len(rows) * plan["epochs"]
        cluster.shutdown(grace_secs=30)
    else:
        cluster.shutdown(timeout=plan["timeout_s"])
    node = cluster.cluster_info[0]
    mgr = TFManager.connect(tuple(node["addr"]),
                            bytes.fromhex(cluster.cluster_meta["authkey_hex"]))
    report.update(mgr.get("chip_smoke"))
    report["claimed_chips"] = node["chips"]
    report["node_state"] = mgr.get("state")
    t0 = time.monotonic()
    sc.stop()
    report["executor_stop_s"] = round(time.monotonic() - t0, 1)
    report["executor_exit_codes"] = [p.exitcode for p in sc._procs]
    report["shm_left"] = sorted(
        f for f in os.listdir("/dev/shm") if f.startswith("tfos_feed_"))
    return report


def run_phase(name: str, plan: dict, out_dir: str, deadline: float) -> dict:
    """Start ``name`` in a fresh driver process and return its report; on
    any failure exit non-zero with the phase name and the child's stderr."""
    timeout = min(plan["timeout_s"], deadline - time.monotonic())
    if timeout <= 0:
        fail(name, "no time left in the run's wall budget")
    err_path = os.path.join(out_dir, f"{name}.stderr")
    t0 = time.monotonic()
    with open(err_path, "w", encoding="utf-8") as err:
        # its own session: a phase that overruns is killed with every
        # process it started (executor, manager, probe child, trainer)
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--phase", name,
             "--plan", json.dumps(plan)],
            stdout=subprocess.PIPE, stderr=err, text=True, cwd=HERE,
            start_new_session=True)
        stdout, timed_out = "", False
        try:
            stdout, _ = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            timed_out = True
        finally:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
    with open(err_path, encoding="utf-8") as f:
        stderr = f.read()
    if timed_out:
        fail(name, f"timed out after {timeout:.0f}s", stderr)
    if proc.returncode != 0:
        fail(name, f"driver exited {proc.returncode}", stderr)
    lines = [ln for ln in stdout.splitlines() if ln.startswith("{")]
    if not lines:
        fail(name, "driver printed no report", stderr)
    report = json.loads(lines[-1])
    report["phase"] = name
    report["phase_wall_s"] = round(time.monotonic() - t0, 1)
    return report


def fail(phase: str, why: str, stderr: str = ""):
    if stderr:
        sys.stderr.write(f"---- stderr of phase {phase} (tail) ----\n"
                         f"{stderr[-6000:]}\n----\n")
    sys.stderr.write(f"chip_smoke: phase {phase} FAILED: {why}\n")
    sys.exit(1)


def check(phase: str, ok: bool, why: str) -> None:
    if not ok:
        fail(phase, why)


def emit(report: dict) -> None:
    print(json.dumps(report), flush=True)


# ---------------------------------------------------------------------------
# Checks: what each phase's report must show
# ---------------------------------------------------------------------------


def phase_devices(chips: int) -> dict:
    """Ask a throw-away child what JAX finds; refuse anything but a TPU
    with enough chips.  The child exits (and lets the chip go) before any
    phase starts."""
    prog = ("import json, jax\n"
            "d = jax.devices()\n"
            "print(json.dumps({'platform': d[0].platform, "
            "'kind': d[0].device_kind, 'count': len(d)}))\n")
    proc = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                          text=True, timeout=300)
    if proc.returncode != 0:
        fail("devices", f"JAX child exited {proc.returncode}", proc.stderr)
    found = json.loads(proc.stdout.strip().splitlines()[-1])
    check("devices", found["platform"] == "tpu",
          f"child reports platform {found['platform']!r} "
          f"({found['kind']}), need 'tpu': no accelerator, no run")
    check("devices", found["count"] >= chips,
          f"need {chips} chip(s), JAX finds {found['count']}")
    found["phase"] = "devices"
    return found


def check_trainer_device(phase: str, report: dict, plan: dict) -> None:
    dev = report["device"]
    check(phase, dev["platform"] == plan["platform"] == dev["backend"],
          f"trainer ran on {dev['platform']!r}, need {plan['platform']!r}")
    check(phase, plan["chips"] in (0, dev["local_count"]),
          f"trainer saw {dev['local_count']} local devices, claimed "
          f"{plan['chips']}")
    check(phase, len(report["claimed_chips"]) == plan["chips"],
          f"claimed {report['claimed_chips']}, asked for {plan['chips']}")
    check(phase, report["node_state"] == "finished",
          f"node state {report['node_state']!r}")
    check(phase, all(c == 0 for c in report["executor_exit_codes"]),
          f"executor exit codes {report['executor_exit_codes']}")


def finite(values) -> bool:
    return all(math.isfinite(v) for v in values)


def check_resnet(phase: str, report: dict, plan: dict) -> None:
    check_trainer_device(phase, report, plan)
    check(phase, report["params"] == plan["params"],
          f"{report['params']} parameters, want {plan['params']}")
    steps_per_epoch = plan["records"] // plan["batch"]
    check(phase, report["steps"] == steps_per_epoch * plan["epochs"],
          f"{report['steps']} steps, want "
          f"{steps_per_epoch * plan['epochs']}")
    check(phase, finite(report["losses"]), f"losses {report['losses']}")


def run_default(args, deadline: float) -> dict:
    data_dir = os.path.join(args.out, "imagenet_tfr")
    if not glob.glob(os.path.join(data_dir, "part-*")):
        from tensorflowonspark_tpu.models import resnet

        resnet.write_synthetic_tfrecords(
            data_dir, RESNET_PLAN["records"], RESNET_PLAN["parts"],
            resnet.Config().image_size, seed=args.seed)

    plan = dict(RESNET_PLAN, data_dir=data_dir, seed=args.seed)
    cold = run_phase("resnet", plan, args.out, deadline)
    emit(cold)
    check_resnet("resnet", cold, plan)
    check("resnet", cold["steps"] - plan["warmup"] >= 8,
          f"only {cold['steps'] - plan['warmup']} timed steps")
    k = plan["records"] // plan["batch"]
    first, last = cold["losses"][:k], cold["losses"][-k:]
    check("resnet", sum(last) < sum(first),
          f"loss did not fall over {plan['epochs']} epochs of one shard: "
          f"first {first}, last {last}")

    plan = dict(FEED_PLAN, seed=args.seed)
    feed = run_phase("feed", plan, args.out, deadline)
    emit(feed)
    check_trainer_device("feed", feed, plan)
    check("feed", feed["rows"] == feed["rows_fed"],
          f"fed {feed['rows_fed']} rows, trainer consumed {feed['rows']}")
    check("feed", feed["shm_bytes"] > 0,
          "no bytes crossed the shm transport "
          f"(pickle bytes {feed['pickle_bytes']})")
    check("feed", not feed["shm_left"], f"leaked {feed['shm_left']}")
    check("feed", finite([feed["first_loss"], feed["final_loss"]])
          and feed["final_loss"] < feed["first_loss"],
          f"loss {feed['first_loss']} -> {feed['final_loss']}")

    plan = dict(WARM_PLAN, data_dir=data_dir, seed=args.seed)
    warm = run_phase("warm", plan, args.out, deadline)
    warm["cold_vs_warm"] = {
        key: [cold[key], warm[key]]
        for key in ("trainer_init_s", "first_step_s")}
    warm["cold_cache"] = cold["cache"]
    emit(warm)
    check_resnet("warm", warm, plan)
    check("warm", warm["cache"]["dir"] == cold["cache"]["dir"],
          f"cache moved: {cold['cache']['dir']} -> {warm['cache']['dir']}")
    check("warm", warm["cache"]["disk_hits"] > 0,
          f"second start found nothing in {warm['cache']['dir']}: "
          f"{warm['cache']}")
    return cold["device"]


def run_multichip(args, deadline: float) -> dict:
    plan4 = dict(MESH_PLAN, chips=4, seed=args.seed)
    def emit_leg(leg: dict) -> None:  # 300-odd leaves: say how many
        emit(dict(leg, opt_state_bytes=f"{len(leg['opt_state_bytes'])} "
                                       "leaves"))

    four = run_phase("mesh4", plan4, args.out, deadline)
    emit_leg(four)
    check_trainer_device("mesh4", four, plan4)
    check("mesh4", four["params"] == plan4["params"],
          f"{four['params']} parameters")
    check("mesh4", four["update_sharded"] and four["n_scatter_buckets"] > 0,
          "the default sharded update did not compile on the 4-chip mesh")
    # The program asks for reduce-scatter + all-gather and no all-reduce
    # (tests/test_collectives.py pins that on CPU).  The TPU compiler keeps
    # the all-gathers and folds the reduce-scatters into all-reduce + slice
    # (PERF.md, PR 21), so of the compiled module only presence is checked.
    asked, made = (four["collectives"][k] for k in ("asked", "compiled"))
    check("mesh4", asked["reduce_scatter"] > 0 and asked["all_gather"] > 0
          and asked["all_reduce"] == 0,
          f"collectives the step asks for: {asked}")
    check("mesh4", made["all-gather"] > 0
          and made["reduce-scatter"] + made["all-reduce"] > 0,
          f"collectives in the compiled step: {made}")
    check("mesh4", finite(four["losses"]), f"losses {four['losses']}")

    plan1 = dict(MESH_PLAN, chips=1, seed=args.seed)
    one = run_phase("mesh1", plan1, args.out, deadline)
    emit_leg(one)
    check_trainer_device("mesh1", one, plan1)
    check("mesh1", finite(one["losses"]), f"losses {one['losses']}")

    rel = [abs(a - b) / abs(b) for a, b in zip(four["losses"],
                                               one["losses"])]
    sharded = [k for k, (_, is_sharded) in four["opt_state_bytes"].items()
               if is_sharded]
    bytes4 = sum(four["opt_state_bytes"][k][0] for k in sharded)
    bytes1 = sum(one["opt_state_bytes"][k][0] for k in sharded)
    compare = {"phase": "compare", "loss_rel_diff": rel,
               "loss_rtol": [MESH_FIRST_LOSS_RTOL, MESH_LOSS_RTOL],
               "sharded_opt_leaves": len(sharded),
               "sharded_opt_bytes_per_device": [bytes4, bytes1],
               "sharded_opt_bytes_ratio": bytes4 / bytes1 if bytes1 else None}
    emit(compare)
    check("compare", rel[0] <= MESH_FIRST_LOSS_RTOL,
          f"the first losses differ by {rel[0]:.3g} relative "
          f"(> {MESH_FIRST_LOSS_RTOL}): {four['losses'][0]} vs "
          f"{one['losses'][0]}")
    check("compare", max(rel) <= MESH_LOSS_RTOL,
          f"loss trajectories differ by {max(rel):.3g} relative "
          f"(> {MESH_LOSS_RTOL}): {four['losses']} vs {one['losses']}")
    check("compare", sharded and 0.24 <= bytes4 / bytes1 <= 0.26,
          f"sharded optimizer leaves hold {bytes4} bytes a device on four "
          f"chips against {bytes1} on one")
    return four["device"]


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run ONLY the four-chip mesh phase and the "
                        "one-chip run it is compared with")
    p.add_argument("--seed", type=int, default=0,
                   help="seed of the generated data (weights: Trainer's)")
    p.add_argument("--out", default=os.path.join(HERE, ".chip_smoke_out"),
                   help="where records, scratch and phase logs are written")
    p.add_argument("--phase", choices=sorted(MAP_FUNS),
                   help=argparse.SUPPRESS)  # internal: a phase's driver
    p.add_argument("--plan", help=argparse.SUPPRESS)
    return p.parse_args(argv)


def main(argv=None) -> None:
    args = parse_args(argv)
    import tensorflowonspark_tpu  # noqa: F401 - outside the repo, fail here
    if args.phase:
        emit(drive_phase(args.phase, json.loads(args.plan)))
        return
    deadline = time.monotonic() + WALL_BUDGET_S
    args.out = os.path.abspath(args.out)  # executors run in their own cwd
    os.makedirs(args.out, exist_ok=True)
    os.environ.setdefault("TFOS_SCRATCH_ROOT",
                          os.path.join(args.out, "scratch"))
    os.makedirs(os.environ["TFOS_SCRATCH_ROOT"], exist_ok=True)
    emit(phase_devices(args.chips))
    run = run_multichip if args.chips == 4 else run_default
    device = run(args, deadline)
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)


if __name__ == "__main__":
    main()
