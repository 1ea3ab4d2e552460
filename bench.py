"""Headline benchmark: prints ONE JSON line with the framework's throughput.

Metric (``BASELINE.json::metric``): ImageNet ResNet-50 images/sec/chip on the
sharded training step (`tensorflowonspark_tpu.trainer.Trainer`) — the same
compiled path the Spark-cluster runtime drives on executors.  Also reports
**MFU** (model FLOPs utilization): compiled FLOPs/step (from XLA's own cost
analysis, analytic fallback) × steps/sec ÷ aggregate peak chip FLOPs.

Fail-soft by design: the measurement runs in a child process under a bounded
timeout; if the primary (accelerator) attempt dies or hangs the parent
retries on the forced-CPU backend and, failing that too, still emits a
parseable diagnostic JSON line and exits 0.  ``parsed`` is never null.

The reference publishes no quantitative numbers (``BASELINE.json::published``
is empty; see ``BASELINE.md``), so ``vs_baseline`` is reported against the
self-set north-star targets below.

Usage::

    python bench.py                      # BOTH halves of BASELINE.json::metric:
                                         # resnet50 images/sec/chip (primary) +
                                         # Criteo wide_deep steps/sec (secondary)
    python bench.py --model wide_deep    # a single model only
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import statistics
import subprocess
import sys
import tempfile
import time

# Self-set targets (images|steps per sec per chip) — the reference published
# nothing, so these anchor vs_baseline at a roofline-informed v5e estimate.
TARGETS = {
    "resnet50": ("images/sec/chip", 2000.0),
    # benchmarked as the CANONICAL architecture since round 5
    # (Config(canonical=True): VALID stem + aux head, ~17 GFLOP/img train
    # — the SAME-padded variant was ~41); at the chip's 0.30-0.35 MFU band
    # the roofline is ~3000-3500 img/s — target set to the band's floor
    "inception_v3": ("images/sec/chip", 3000.0),
    # ~1.14 GFLOP fwd/img (≈1/7th of resnet50's compute) but depthwise
    # convs run on the VPU, capping MFU well below ResNet's band —
    # target ≈ 3× resnet
    "mobilenet_v1": ("images/sec/chip", 6000.0),
    "wide_deep": ("steps/sec", 100.0),  # see TARGET_NOTES["wide_deep"]
    "bert": ("examples/sec/chip", 100.0),
    "mnist_mlp": ("images/sec/chip", 100000.0),
    "cifar10_cnn": ("images/sec/chip", 20000.0),
}

# Machine-readable context for targets whose shortfall is a property of THIS
# chip, not the framework — carried into the JSON artifact so the number is
# interpretable without opening BENCH_NOTES.md (VERDICT r3 weak #2).
TARGET_NOTES = {
    "wide_deep": (
        "re-baselined (BASELINE.md 'wide_deep re-baseline'): the sanctioned "
        "config is pinned batch 1024, where this chip measures ~103 steps/s "
        "against the 100 steps/s target. steps/sec is floored by the chip's "
        "measured ~16-20 ms scatter per ~100k embedding rows per step "
        "(BENCH_NOTES.md 'Sparse vs dense table updates'), not by the "
        "framework; examples_per_sec is the saturating metric (~176k "
        "examples/s at batch 4096, where the per-index scatter floor "
        "amortizes)."
    ),
}

# Per-chip auto batch sizes on accelerators (CPU fallback uses 16).  The CTR
# model is bandwidth-bound (embedding gathers + dense optimizer update over
# the fused table), so it wants a much larger batch than the conv nets.
ACCEL_BATCH = {
    "resnet50": 128,
    "inception_v3": 128,
    "mobilenet_v1": 256,
    # pinned at the SANCTIONED re-baseline config (BASELINE.md): steps/sec
    # is the headline metric and 1024 is the batch the 100 steps/s target
    # is quoted at; the saturating examples/s rate at 4096 is recorded in
    # TARGET_NOTES instead of silently changing the benchmarked config
    "wide_deep": 1024,
    "bert": 32,
    "mnist_mlp": 512,
    "cifar10_cnn": 256,
}

# Peak dense bf16 FLOP/s per chip, keyed by a substring of device_kind.
# (MFU is conventionally quoted against the bf16 matmul peak.)
PEAK_FLOPS = [
    ("v5 lite", 197e12), ("v5e", 197e12),
    ("v5p", 459e12), ("v5", 459e12),
    ("v6", 918e12), ("trillium", 918e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
]

_PRIMARY_TIMEOUT_S = 420  # healthy worst case is ~200 s (import +
# compile + 20 steps); 2× headroom.  The round-3/4 value of 900 was both
# unreachable under the wall budget below and the direct cause of the
# round-4 empty artifact (a wedged chip burned 900 s twice).
_FALLBACK_TIMEOUT_S = 420

# Outage-proofing (VERDICT r4 weak #1): the round-4 chip wedge burned the
# full primary timeout twice and the driver's budget expired before the CPU
# fallback finished — BENCH_r04.json carried no number.  Three defenses:
#   1. a ~60 s liveness probe (tiny jit'd matmul in a subprocess) runs before
#      ANY primary attempt; a wedged chip fails the probe fast and the run
#      goes straight to the CPU fallback, stamped ``degraded``;
#   2. the whole headline run (probe + primaries + fallbacks) lives under a
#      hard wall-clock budget — every child timeout is clipped to the time
#      remaining minus a reserve for the fallbacks still owed;
#   3. one health verdict is shared across models: if the probe (or a
#      primary attempt) reveals a hung accelerator, later models skip their
#      primary instead of re-burning the timeout.
# A fourth defense (round 5): the observed outage FLAPS — the chip came back
# for a ~5-minute healthy window mid-wedge and wedged again — so the t=0
# probe verdict is not final.  When the initial probe failed, the headline
# run re-probes once between its two halves (the first model's CPU fallback
# has burned a few minutes by then); a green second verdict wins wide_deep a
# real on-chip number instead of inheriting a stale degraded stamp.  A hung
# PRIMARY after a green probe is different evidence — tiny probe ops succeed
# while real work hangs — so that verdict is NOT retried.
# Env knobs exist so CI can simulate the outage (see tests/test_bench.py):
#   TFOS_BENCH_SIMULATE_HANG=N  → the first N accelerator-path children
#     sleep forever (N=big → permanent wedge; N=1 → flapping chip whose
#     probe hangs once); forced-CPU children always run
#   TFOS_BENCH_WALL_BUDGET_S / TFOS_BENCH_PROBE_TIMEOUT_S → shrink budgets
_PROBE_TIMEOUT_S = int(os.environ.get("TFOS_BENCH_PROBE_TIMEOUT_S", "60"))
_WALL_BUDGET_S = int(os.environ.get("TFOS_BENCH_WALL_BUDGET_S", "660"))
# held back per still-owed CPU fallback (tiny configs compile+run well
# inside this) so a hung primary can never starve the fallback
_FALLBACK_RESERVE_S = int(os.environ.get("TFOS_BENCH_FALLBACK_RESERVE_S",
                                         "120"))
_MIN_CHILD_S = 20  # below this, don't bother spawning a child


@contextlib.contextmanager
def _flight_disabled():
    """Run with the flight recorder off (``TFOS_FLIGHT=0``, previous value
    restored) — the off half of the recorder-overhead A/B both
    microbenches stamp."""
    prev = os.environ.get("TFOS_FLIGHT")
    os.environ["TFOS_FLIGHT"] = "0"
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("TFOS_FLIGHT", None)
        else:
            os.environ["TFOS_FLIGHT"] = prev


@contextlib.contextmanager
def _trace_requests_disabled():
    """Run with request-scoped tracing off (``TFOS_TRACE_REQUESTS=0``,
    previous value restored) — the off half of the tracing-overhead A/B
    the online microbench stamps as ``trace_overhead_frac``."""
    prev = os.environ.get("TFOS_TRACE_REQUESTS")
    os.environ["TFOS_TRACE_REQUESTS"] = "0"
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("TFOS_TRACE_REQUESTS", None)
        else:
            os.environ["TFOS_TRACE_REQUESTS"] = prev


class _Deadline:
    """Hard wall-clock budget for the whole bench invocation."""

    def __init__(self, budget_s: float):
        self._end = time.monotonic() + budget_s

    def remaining(self) -> float:
        return max(0.0, self._end - time.monotonic())

    def clip(self, timeout_s: float, reserve_s: float = 0.0) -> float:
        """Largest timeout ≤ ``timeout_s`` that leaves ``reserve_s`` spare."""
        return min(float(timeout_s), self.remaining() - reserve_s)


def _simulate_hang_requested(force_cpu: bool) -> bool:
    """First-N-children hang simulation (child side).

    ``TFOS_BENCH_SIMULATE_HANG=N``: the first N accelerator-path children of
    this bench invocation hang; later ones run normally — modelling both the
    permanent wedge (N ≥ number of children) and the round-5 flapping chip
    (N=1: the probe hangs, the mid-run re-probe finds the chip back).
    Sequential children share a parent-created counter file; without one
    (child invoked directly), every accelerator child hangs.
    """
    try:
        n = int(os.environ.get("TFOS_BENCH_SIMULATE_HANG") or 0)
    except ValueError:
        # legacy truthy style ("true", "yes"): preserve the old semantics —
        # EVERY accelerator child hangs (permanent wedge), not just one
        n = sys.maxsize
    if not n or force_cpu:
        return False
    counter = os.environ.get("TFOS_BENCH_HANG_COUNTER_FILE")
    if not counter:
        return True
    used = os.path.getsize(counter) if os.path.exists(counter) else 0
    if used >= n:
        return False
    with open(counter, "ab") as f:
        f.write(b"x")
    return True


def _setup_hang_counter() -> None:
    """Parent side: create the shared counter file for first-N semantics."""
    if (os.environ.get("TFOS_BENCH_SIMULATE_HANG")
            and not os.environ.get("TFOS_BENCH_HANG_COUNTER_FILE")):
        import atexit

        fd, path = tempfile.mkstemp(prefix="tfos_bench_hang_")
        os.close(fd)
        os.environ["TFOS_BENCH_HANG_COUNTER_FILE"] = path
        atexit.register(lambda: os.path.exists(path) and os.unlink(path))


def _parse_args(argv=None):
    p = argparse.ArgumentParser()
    # default None = "the headline run": resnet50 primary + wide_deep secondary
    p.add_argument("--model", default=None, choices=sorted(TARGETS))
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--steps", type=int, default=None)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--feed", action="store_true",
                   help="measure feed/compute overlap of the input pipeline "
                        "(SURVEY §3.2 hard part (b)) instead of throughput")
    p.add_argument("--feed-transport", action="store_true",
                   help="measure the feeder→DataFeed transport alone: "
                        "rows/sec through the real TFManager data plane, "
                        "shm columnar vs legacy pickled rows (host-side, "
                        "no accelerator involved)")
    p.add_argument("--serving", action="store_true",
                   help="measure the TFModel.transform serving data plane: "
                        "rows/sec through the real _RunModel path, bucketed "
                        "columnar pipeline vs the legacy row loop "
                        "(host-side, no accelerator involved)")
    p.add_argument("--serving-online", action="store_true",
                   help="measure the continuous-batching online tier: "
                        "closed-loop rows/sec of N concurrent clients "
                        "through the real coalescer → bucketed forward → "
                        "scatter path vs N independent single-request "
                        "callers at the same p99 SLO (host-side, no "
                        "accelerator involved)")
    p.add_argument("--serving-decode", action="store_true",
                   help="measure the generative-decode tier: closed-loop "
                        "aggregate tokens/sec through the continuous-"
                        "batching engine (paged KV pool) vs sequential "
                        "per-request decode, token-level output equality "
                        "checked, TTFT/ITL p99 SLO-bound")
    p.add_argument("--decode-prefill", action="store_true",
                   help="measure chunked batched prefill + COW prefix "
                        "sharing on the decode tier: short-prompt TTFT "
                        "p99 under an interleaved short/long mix vs the "
                        "legacy per-prompt-prefill engine, plus unique "
                        "KV pages allocated for N shared-prefix requests "
                        "both ways (sub-linear with sharing), token-level "
                        "output equality checked (host-side, no "
                        "accelerator involved)")
    p.add_argument("--decode-spec", action="store_true",
                   help="measure speculative multi-token decoding on the "
                        "paged decode tier: n-gram drafted tokens verified "
                        "in one fixed-shape call vs the single-token "
                        "engine, ITL p99 ratio (lower is better) + tokens "
                        "per verify step + drafter acceptance rate, "
                        "token-level output equality checked (host-side, "
                        "no accelerator involved)")
    p.add_argument("--serving-mesh", action="store_true",
                   help="measure the multi-host serving mesh: aggregate "
                        "closed-loop rows/sec of N replica PROCESSES "
                        "behind the placement router vs the same workload "
                        "through one in-process server, plus router-hop "
                        "latency and a SIGKILL zero-loss chaos pass "
                        "(host-side, no accelerator involved)")
    p.add_argument("--fleet-obs", action="store_true",
                   help="measure the fleet observability plane: router "
                        "p99 A/B'd collector-on/off "
                        "(fleet_overhead_frac), an induced hot replica "
                        "asserted to raise a fleet.load_skew finding "
                        "within one scrape cadence, and /fleet/metrics "
                        "schema-validated — through N replica PROCESSES "
                        "behind the real router (host-side, no "
                        "accelerator involved)")
    p.add_argument("--incident", action="store_true",
                   help="measure the fleet incident plane: router p99 "
                        "A/B'd journal-on/off (incident_overhead_frac, "
                        "expected at the noise floor — journal events "
                        "are control-plane transitions, never "
                        "per-request rows), then SIGKILL a replica "
                        "under traceparent-armed SLO-breaching load and "
                        "reconstruct ONE causally-ordered timeline from "
                        "the spool via tools/incident.py: death event "
                        "with the corpse's stamped last-flush, "
                        "generation-fenced regroup, ≥1 exemplar-linked "
                        "recovered trace (host-side, no accelerator "
                        "involved)")
    p.add_argument("--costs", action="store_true",
                   help="measure the cost-accounting plane: the "
                        "conservation identity (Σ per-tenant "
                        "device-seconds + pad = engine seconds, within "
                        "1%% under concurrent mixed-tenant online + "
                        "decode load), caller p99 A/B'd ledger-on/off "
                        "(costs_overhead_frac, expected at the noise "
                        "floor), an induced dominant tenant asserted to "
                        "raise a fleet.cost_skew finding within one "
                        "judgment cadence, and the goodput breakdown of "
                        "a short training run reconciled to measured "
                        "wall (in-process, no accelerator involved)")
    p.add_argument("--step-collectives", action="store_true",
                   help="A/B the bucketed, overlapped gradient-collective "
                        "train step against the monolithic GSPMD step on "
                        "the local device set: rows/sec both ways, an "
                        "output-equality check, and allreduce overlap "
                        "efficiency against the delivered ICI bandwidth "
                        "(null + reason on a single device)")
    p.add_argument("--collectives", action="store_true",
                   help="compare the reduce-scatter + sharded-update + "
                        "all-gather exchange against the bucketed "
                        "all-reduce: analytic bytes ratio (numeric on any "
                        "box), 4-step output equality, and rows/sec both "
                        "ways on ≥2 local devices (equality and "
                        "throughput null + reason on a single device)")
    p.add_argument("--recovery", action="store_true",
                   help="measure executor-loss recovery: seconds from "
                        "SIGKILLing one of three trainers mid-run to the "
                        "first post-restore step, through the real elastic "
                        "regroup + checkpoint-restore path (host-side, "
                        "local substrate)")
    p.add_argument("--compile-cache", action="store_true",
                   help="measure second-process cold start A/B'd against "
                        "the persistent compile cache: spawn a fresh "
                        "process, load + warm the same tenant/ladder "
                        "through the real OnlineServer path, time to "
                        "first served request — once reading a seeded "
                        "JAX_COMPILATION_CACHE_DIR and once cache-off "
                        "(host-side, CPU children)")
    p.add_argument("--_measure", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--_probe", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--_force-cpu", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--_coldstart", default=None, help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if args.feed and args.model is not None:
        p.error("--feed measures the resnet50 input pipeline; "
                "--model is not supported with it")
    return args


def _peak_flops(device_kind: str) -> float | None:
    kind = device_kind.lower()
    for key, peak in PEAK_FLOPS:
        if key in kind:
            return peak
    return None


def _analytic_flops(model: str, config, batch_size: int) -> float | None:
    """Analytic FLOPs/step fallback when XLA cost analysis is n/a.

    Train step ≈ 3× forward (fwd + 2× bwd).  Only the full-size configs the
    constants were derived for are claimed; a tiny/test config returns None
    rather than a number off by orders of magnitude.
    """
    if model == "resnet50" and getattr(config, "image_size", 0) == 224 and \
            tuple(getattr(config, "stage_sizes", ())) == (3, 4, 6, 3):
        return 3.0 * 8.2e9 * batch_size  # ~4.1 GMACs fwd per 224x224 image
    if model == "inception_v3" and getattr(config, "image_size", 0) == 299 \
            and getattr(config, "width_mult", 0) == 1.0:
        # per-variant constants from XLA cost analysis: canonical
        # (VALID stem + aux head) ≈ 3 × 5.7 GFLOP fwd/img; the SAME-padded
        # variant ≈ 3 × 13.7 (see models/inception.py module docstring)
        if getattr(config, "canonical", False):
            return 3.0 * 5.7e9 * batch_size
        return 3.0 * 13.7e9 * batch_size
    if model == "mobilenet_v1":
        # derived from the block table for ANY width/image size
        from tensorflowonspark_tpu.models import mobilenet

        return 3.0 * mobilenet.analytic_fwd_flops(config) * batch_size
    if model == "wide_deep":
        # derived, not a constant: MLP matmul chain dominates the countable
        # FLOPs (the gathers/optimizer update are bandwidth, not FLOPs)
        from tensorflowonspark_tpu.models import widedeep as wd

        dims = [wd.NUM_CAT * config.embed_dim + wd.NUM_DENSE,
                *config.hidden, 1]
        fwd = 2.0 * sum(a * b for a, b in zip(dims, dims[1:]))
        return 3.0 * fwd * batch_size
    return None


def measure(args) -> dict:
    """Run the timed measurement in-process and return the result dict."""
    if args._force_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("TFOS_NUM_CHIPS", "0")
    from tensorflowonspark_tpu import util

    util.ensure_jax_platform()
    import jax

    from tensorflowonspark_tpu import models as model_zoo
    from tensorflowonspark_tpu.trainer import Trainer

    platform = jax.default_backend()
    on_accel = platform in ("tpu", "gpu")
    n_chips = len(jax.devices())

    lib = model_zoo.get_model(args.model)
    # inception benches the canonical architecture (acceptance config #3
    # names Inception-v3; the SAME-padded variant needed an asterisk)
    full_kwargs = {"inception_v3": {"canonical": True}}.get(args.model, {})
    config = lib.Config(**full_kwargs) if on_accel else lib.Config.tiny()
    batch_size = args.batch_size
    if batch_size is None:
        batch_size = (ACCEL_BATCH[args.model] if on_accel else 16) * max(1, n_chips)
    steps = args.steps
    if steps is None:
        steps = 20 if on_accel else 5

    print(
        f"bench: model={args.model} platform={platform} chips={n_chips} "
        f"batch={batch_size} steps={steps}",
        file=sys.stderr,
    )

    trainer = Trainer(args.model, config=config)
    batch = lib.example_batch(config, batch_size=batch_size)
    device_batch = trainer.shard(batch)  # input pipeline is measured separately

    # AOT-compile ONCE and reuse the executable for both cost analysis and
    # the timing loop (a separate .lower().compile() would not populate the
    # jit dispatch cache and would double compile time).
    step_fn = trainer.train_step
    flops_per_step = None  # GLOBAL flops across all chips
    try:
        compiled = step_fn.lower(trainer.state, device_batch).compile()
        step_fn = compiled
        f = compiled.cost_analysis().get("flops")
        if f and f > 0:
            # cost_analysis reports the per-device (post-SPMD) program
            flops_per_step = float(f) * n_chips
    except Exception as e:  # AOT/cost analysis is best-effort on some backends
        print(f"bench: AOT compile/cost_analysis unavailable ({e!r})",
              file=sys.stderr)
    if flops_per_step is None:
        flops_per_step = _analytic_flops(args.model, config, batch_size)

    def fetch_loss(loss):
        """Host round-trip of the loss, tolerant of None (steps=0) and
        non-scalar losses (per-device replicas)."""
        if loss is None:
            return None
        import numpy as np

        return float(np.asarray(jax.device_get(loss)).mean())

    def timed_loop(state, sync_each_step):
        loss = None
        t0 = time.perf_counter()
        for _ in range(steps):
            state, loss = step_fn(state, device_batch)
            if sync_each_step:
                fetch_loss(loss)  # hard host round-trip per step
        # fetch the actual bytes, not just block_until_ready: the final loss
        # data-depends on every step, and a remote backend can ack readiness
        # without finishing, but it cannot hand back a value it hasn't
        # computed
        fetch_loss(loss)
        return state, loss, time.perf_counter() - t0

    state = trainer.state
    loss = None
    for _ in range(args.warmup):
        state, loss = step_fn(state, device_batch)
    fetch_loss(loss)

    state, loss, dt = timed_loop(state, sync_each_step=False)

    unit, target = TARGETS[args.model]
    peak = _peak_flops(jax.devices()[0].device_kind) if on_accel else None

    def derive(dt):
        steps_per_sec = steps / dt
        value = (steps_per_sec if unit == "steps/sec"
                 else steps_per_sec * batch_size / n_chips)
        mfu = (flops_per_step * steps_per_sec / (peak * n_chips)
               if peak and flops_per_step else None)
        return steps_per_sec, value, mfu

    steps_per_sec, value, mfu = derive(dt)
    synced = False
    if mfu is not None and mfu > 1.0:
        # >100% of peak is physically impossible: the backend acked the
        # dispatches without finishing them (block_until_ready lied).
        # Re-time forcing a host round-trip of the loss each step so every
        # step provably completed.
        print(f"bench: async timing gave impossible MFU {mfu:.2f}; "
              "re-timing with per-step host sync", file=sys.stderr)
        state, loss, dt = timed_loop(state, sync_each_step=True)
        steps_per_sec, value, mfu = derive(dt)
        synced = True

    final_loss = fetch_loss(loss)
    result = {
        "metric": f"{args.model}_{unit.replace('/', '_per_').replace('.', '')}",
        "value": round(value, 2),
        "unit": unit,
        "vs_baseline": round(value / target, 4),
        "platform": platform,
        "n_chips": n_chips,
        "batch_size": batch_size,
        # 6 significant digits, not fixed decimals: a model that memorizes
        # the single repeated bench batch reaches losses ≪ 1e-4, and a
        # fixed-decimal rounding to 0.0 reads as "broken"
        "loss": (float(f"{final_loss:.6g}") if final_loss is not None
                 else None),
    }
    if unit == "steps/sec":
        # steps/sec alone undersells throughput-shaped models: carry the
        # examples rate so the artifact is interpretable standalone
        result["examples_per_sec"] = round(steps_per_sec * batch_size, 1)
    if args.model in TARGET_NOTES:
        result["target_note"] = TARGET_NOTES[args.model]
    if mfu is not None:
        result["mfu"] = round(mfu, 4)
        if mfu > 1.0:
            result["timing_suspect"] = True  # impossible even after sync
    if synced:
        result["synced_timing"] = True
    if flops_per_step is not None:
        result["flops_per_step"] = flops_per_step
    _stamp_roofline(result)
    return result


def _stamp_roofline(result: dict) -> None:
    """Measure delivered HBM/ICI bandwidth and stamp it beside MFU.

    Runs AFTER the timing loop (so the probe never pollutes the headline
    measurement) on whatever backend the child actually used — a CPU
    fallback stamps its own (CPU) bandwidth, keeping the schema total.
    The roofline verdict is what re-litigates a low MFU: measured-bw near
    datasheet with MFU stuck at 0.30 indicts the framework; degraded
    measured-bw indicts the chip (VERDICT r5).
    """
    try:
        from tensorflowonspark_tpu.obs import roofline

        rf = roofline.probe()
    except Exception as e:  # fail-soft: the number line must still come out
        rf = {"mem_bw_gbps": None, "ici_bw_gbps": None,
              "mem_bw_reason": f"roofline probe crashed: {e!r}"[:200],
              "ici_bw_reason": f"roofline probe crashed: {e!r}"[:200]}
    for key in ("mem_bw_gbps", "mem_bw_elementwise_gbps",
                "mem_bw_reduction_gbps", "mem_bw_frac_of_peak",
                "hbm_peak_gbps", "mem_bw_reason", "ici_bw_gbps",
                "ici_bw_reason", "roofline_probe_s"):
        src = "probe_s" if key == "roofline_probe_s" else key
        if src in rf:
            result[key] = rf[src]
    for key in ("mem_bw_gbps", "ici_bw_gbps"):  # schema is total
        result.setdefault(key, None)


def _ensure_roofline_fields(result: dict, reason: str) -> None:
    """Parent-side backstop: every emitted half carries the roofline keys.

    Children that ran :func:`measure` stamped real values; a stub half
    (no child succeeded) gets an explicit ``null`` + reason so the BENCH
    schema stays total even for fully-degraded runs.
    """
    for half in (result, result.get("secondary")):
        if not isinstance(half, dict):
            continue
        for key, reason_key in (("mem_bw_gbps", "mem_bw_reason"),
                                ("ici_bw_gbps", "ici_bw_reason")):
            if key not in half:
                half[key] = None
                half.setdefault(reason_key, reason)


def measure_feed(args) -> dict:
    """Prove feed/compute overlap on the REAL input pipeline.

    Times three passes over the same synthetic ImageNet-shaped TFRecords:
    feed-only (readers pipeline, no training), compute-only (device-resident
    batch), and overlapped (prefetch=2, batches staged onto the mesh by the
    pipeline thread while the previous batch trains).  Overlap is proven
    when overlapped ≈ max(feed, compute) rather than their sum.
    """
    if args._force_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("TFOS_NUM_CHIPS", "0")
    import tempfile

    from tensorflowonspark_tpu import util

    util.ensure_jax_platform()
    import jax

    from tensorflowonspark_tpu import models as model_zoo

    platform = jax.default_backend()
    on_accel = platform in ("tpu", "gpu")
    lib = model_zoo.get_model("resnet50")
    config = lib.Config() if on_accel else lib.Config.tiny()
    side = config.image_size
    # per-batch work must dwarf the ~0.3 ms thread handoff for the overlap
    # signal to be measurable; the tiny CPU config needs a big batch
    batch_size = args.batch_size or (64 if on_accel else 512)
    n_batches = 12

    tmpdir = tempfile.mkdtemp(prefix="tfos_feed_")
    try:
        return _measure_feed_body(tmpdir, lib, config, side, batch_size,
                                  n_batches, platform, on_accel)
    finally:
        import shutil

        shutil.rmtree(tmpdir, ignore_errors=True)


def _measure_feed_body(tmpdir, lib, config, side, batch_size, n_batches,
                       platform, on_accel) -> dict:
    import jax

    from tensorflowonspark_tpu import readers
    from tensorflowonspark_tpu.models import resnet
    from tensorflowonspark_tpu.trainer import Trainer

    files = resnet.write_synthetic_tfrecords(
        tmpdir, batch_size * n_batches, parts=4, side=side)

    trainer = Trainer("resnet50", config=config)

    def batches(prefetch):
        return readers.tfrecord_batches(
            files, batch_size, parse_fn=resnet.tfrecord_parse_fn(side),
            drop_remainder=True, readers=2, prefetch=prefetch,
            device_put=trainer.shard)

    # compile once
    warm = trainer.shard(lib.example_batch(config, batch_size=batch_size))
    state, loss = trainer.state, None
    for _ in range(2):
        state, loss = trainer.train_step(state, warm)
    jax.block_until_ready(loss)

    t0 = time.perf_counter()
    n = 0
    for b in batches(prefetch=0):
        jax.block_until_ready(jax.tree_util.tree_leaves(b)[0])
        n += 1
    feed_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(n):
        state, loss = trainer.train_step(state, warm)
    jax.block_until_ready(loss)
    compute_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for b in batches(prefetch=2):
        state, loss = trainer.train_step(state, b)
    jax.block_until_ready(loss)
    overlapped_s = time.perf_counter() - t0

    serial = feed_s + compute_s
    ideal = max(feed_s, compute_s)
    # 1.0 = perfect overlap (wall == max); 0.0 = fully serialized (== sum)
    efficiency = (serial - overlapped_s) / max(serial - ideal, 1e-9)
    result = {
        "metric": "feed_compute_overlap_efficiency",
        "value": round(min(max(efficiency, 0.0), 1.5), 4),
        "unit": "fraction",
        "vs_baseline": round(min(max(efficiency, 0.0), 1.5), 4),
        "platform": platform,
        "batch_size": batch_size,
        "n_batches": n,
        "feed_only_s": round(feed_s, 4),
        "compute_only_s": round(compute_s, 4),
        "overlapped_s": round(overlapped_s, 4),
        "serial_sum_s": round(serial, 4),
        "ideal_max_s": round(ideal, 4),
    }
    if not on_accel:
        # on the CPU backend the parse threads and XLA compute share the
        # same cores — there is no second device to overlap against, so
        # wall ≈ sum regardless of pipeline correctness (the sleep-based
        # unit tests in tests/test_readers.py / test_datafeed.py isolate
        # the mechanism instead)
        result["limitation"] = "cpu backend: feed and compute share cores"
    _stamp_roofline(result)
    return result


def measure_feed_transport(rows_total: int = 4096, chunk_rows: int = 256,
                           batch_size: int = 1024,
                           feature_dim: int = 16384) -> dict:
    """Feed microbench: rows/sec through the REAL feeder→DataFeed path.

    Same wire as SPARK-mode training — chunks encoded feeder-side
    (``shm.encode_chunk``), pushed through a live TFManager server process,
    consumed by ``DataFeed.next_batch`` — once over the legacy pickled-rows
    transport (every chunk pickled twice across the manager, per-row
    consumer columnarization) and once over the shm columnar transport
    (feeder-side columnarization, descriptor-only queue).  The ratio is the
    serialization wall the zero-copy data plane removed; host-side and
    CPU-only, so the number is valid even on accelerator-degraded runs.

    Default rows are 64 KiB of float32 features (training-shaped payloads,
    between CIFAR and ImageNet rows): the wall scales with row bytes, and
    tiny rows are queue-latency-bound on both transports — see
    BENCH_NOTES.md "Feed transport microbench" for the measured size sweep.

    From r09 every measurement also carries its flight-recorder stage
    breakdown (``feed_stage_breakdown``: consumer ``wait``/``ingest``
    seconds summing to the measured wall within the gate tolerance, plus
    the bottleneck verdict and the feeder thread's concurrent
    ``encode``/``backpressure`` split) and the recorder's measured
    overhead (``feed_flight_overhead_frac``: one extra shm pass with
    ``TFOS_FLIGHT=0``).
    """
    import threading

    import numpy as np

    from tensorflowonspark_tpu import TFManager, marker, shm
    from tensorflowonspark_tpu.TFNode import DataFeed
    from tensorflowonspark_tpu.obs import flight

    rng = np.random.default_rng(0)
    feats = rng.standard_normal((rows_total, feature_dim)).astype(np.float32)
    rows = [(feats[i], i) for i in range(rows_total)]
    rec = flight.recorder("feed")
    feeder_rec = flight.recorder("feeder")

    def run(transport: str) -> tuple[float, dict]:
        rec.reset()
        feeder_rec.reset()
        m = TFManager.start(b"feed-transport-bench",
                            ["input", "output", "error"], mode="local")
        try:
            q = m.get_queue("input")
            fallbacks = [0]
            feeder_err: list = [None]

            def feeder() -> None:
                # proxies keep per-thread connections: safe from a thread.
                # Any failure must still deliver StopFeed, or the consumer
                # loop blocks forever on a healthy-but-starved queue and
                # the whole bench wedges with no artifact — the exact
                # failure mode the harness exists to prevent.
                try:
                    for i in range(0, rows_total, chunk_rows):
                        te = time.perf_counter()
                        payload = shm.encode_chunk(rows[i:i + chunk_rows],
                                                   transport=transport)
                        if (transport == "shm"
                                and not isinstance(payload,
                                                   shm.ShmChunkRef)):
                            fallbacks[0] += 1  # write_chunk fell back
                        tp = time.perf_counter()
                        q.put(payload)
                        feeder_rec.add(
                            encode=tp - te,
                            backpressure=time.perf_counter() - tp)
                        feeder_rec.commit()
                except BaseException as e:  # noqa: BLE001 - re-raised below
                    feeder_err[0] = e
                finally:
                    try:
                        q.put(marker.StopFeed())
                    except Exception:
                        pass  # manager gone: consumer's get will raise

            feed = DataFeed(m, input_mapping=["x", "y"])
            th = threading.Thread(target=feeder, daemon=True)
            t0 = time.perf_counter()
            th.start()
            n = 0
            while not feed.should_stop():
                batch = feed.next_batch(batch_size)
                if batch:
                    n += int(batch["y"].shape[0])
                rec.commit()  # one flight record per consumed batch
            dt = time.perf_counter() - t0
            th.join(timeout=30)
            if feeder_err[0] is not None:
                raise RuntimeError(
                    f"feed transport bench feeder failed: "
                    f"{feeder_err[0]!r}") from feeder_err[0]
            if n != rows_total:
                raise RuntimeError(
                    f"feed transport bench lost rows: {n}/{rows_total}")
            if fallbacks[0]:
                # a number measured on a mixed shm/pickle wire must not be
                # stamped with feed_transport="shm" — the gate compares
                # within a transport; fail loudly into null + reason
                raise RuntimeError(
                    f"shm transport fell back to pickled columnar on "
                    f"{fallbacks[0]} chunk(s) (/dev/shm full or "
                    "unwritable?) — refusing to mislabel the measurement")
            breakdown = rec.breakdown(dt)
            # the feeder thread runs concurrent with the consumer wall:
            # its split is evidence (encode vs queue back-pressure), not
            # part of the additive stage sum
            breakdown["feeder_stages_s"] = {
                k: round(v, 4)
                for k, v in sorted(feeder_rec.totals().items())}
            return rows_total / dt, breakdown
        finally:
            m.shutdown()

    out = {
        "feed_rows_total": rows_total,
        "feed_chunk_rows": chunk_rows,
        "feed_batch_size": batch_size,
        "feed_row_bytes": int(feats[0].nbytes + 8),
    }
    recording = flight.enabled()
    pickle_rps, pickle_bd = run("rows")
    out["feed_rows_per_sec_pickle"] = round(pickle_rps, 1)
    if shm.shm_available():
        shm_rps, shm_bd = run("shm")
        out["feed_rows_per_sec"] = round(shm_rps, 1)
        out["feed_transport"] = "shm"
        out["feed_transport_speedup"] = round(shm_rps / pickle_rps, 2)
        out["feed_stage_breakdown"] = shm_bd if recording else None
        if recording:
            # recorder cost, measured the only honest way: the same pass
            # with TFOS_FLIGHT=0.  Order-alternated pairs (off, off, then
            # a second on) so cache/allocator warmth from a preceding
            # pass hits both sides — a single fixed-order off-run after
            # the recorded one would read its warm-state advantage as
            # recorder cost
            with _flight_disabled():
                off_rps, _ = run("shm")
                off2_rps, _ = run("shm")
            on2_rps, _ = run("shm")
            out["feed_flight_overhead_frac"] = round(
                1.0 - max(shm_rps, on2_rps) / max(off_rps, off2_rps), 4)
    else:
        out["feed_rows_per_sec"] = round(pickle_rps, 1)
        out["feed_transport"] = "pickle"
        out["feed_transport_reason"] = ("shared memory unavailable on this "
                                        "host; pickled columnar fallback")
        out["feed_stage_breakdown"] = pickle_bd if recording else None
    if not recording:
        # the opted-out run cannot decompose its wall: explicit null +
        # reason keeps the r09 schema total without failing the gate's
        # reconciliation on an all-zero sum
        out["feed_stage_breakdown_reason"] = (
            "flight recorder disabled (TFOS_FLIGHT=0)")
    return out


def measure_serving(rows_total: int = 16384, feature_dim: int = 256,
                    batch_size: int = 1024, out_dim: int = 8,
                    reps: int = 5) -> dict:
    """Serving microbench: rows/sec through the REAL ``_RunModel`` path.

    Drives the exact ``mapPartitions`` closure of ``TFModel.transform``
    over ragged-tailed partitions of the same logical rows, once per data
    plane:

    - **bucketed** — the serving data plane end to end: Arrow-shaped
      partition elements (what real pyspark hands over under
      ``df.mapInArrow`` / Arrow serialization; zero-per-row columnar
      ingest through ``sql_compat.arrow_batch_columns``), pad-and-mask to
      one compiled bucket shape, prefetch-pumped ``device_put``, one
      ``tolist`` per output column.  When pyarrow is unavailable the
      bucketed plane ingests the Row-shaped partitions instead
      (``serve_ingest: "rows"`` — a different, slower experiment, which
      is why the gate only compares same-``serve_ingest`` runs).
    - **legacy** — the pre-bucketing row loop over Row-shaped partitions
      (the only form it accepts): per-row ``row[col]`` ingest, ragged
      tails compiled at their own size, per-cell ``_pyval`` emission.

    Both planes score the same rows through the same jitted forward and
    the outputs are checked equal before either number is stamped.
    Host-side (CPU backend works), so the number stays valid on
    accelerator-degraded runs.

    Timing is steady-state and best-of-``reps`` per plane (this 2-core
    container suffers multi-x contention noise): both planes run once
    un-timed first, so the ratio measures the per-row data-plane wall,
    not XLA compile time — the compile win is reported separately as
    ``serving_compiles_total`` (bucketed plane: == bucket count,
    regardless of how many distinct partition-tail sizes the geometry
    produced).

    Default rows are 1 KiB of float32 features (feature_dim 256 — a CTR /
    embedding-model serving shape); see BENCH_NOTES.md "Serving data
    plane microbench" for the measured geometry sweep.
    """
    import shutil

    import numpy as np

    from tensorflowonspark_tpu import compat, obs, pipeline, serving
    from tensorflowonspark_tpu.sparkapi.sql import Row

    rng = np.random.default_rng(0)
    w = rng.standard_normal((feature_dim, out_dim)).astype(np.float32)
    feats = rng.standard_normal((rows_total, feature_dim)).astype(np.float32)
    rows = [Row.from_fields(["features", "id"], [feats[i], i])
            for i in range(rows_total)]
    # ragged partitions: every tail a DISTINCT size — on the legacy path
    # each distinct tail is a fresh XLA compile, on the bucketed path they
    # all pad to the one batch_size bucket
    bounds: list[tuple[int, int]] = []
    start, i = 0, 0
    while start < rows_total:
        size = min(4 * batch_size + 31 + 17 * i, rows_total - start)
        bounds.append((start, start + size))
        start += size
        i += 1
    row_parts = [rows[a:b] for a, b in bounds]
    try:
        import pyarrow as pa

        ids = np.arange(rows_total, dtype=np.int64)
        arrow_parts = [
            [pa.RecordBatch.from_arrays(
                [pa.array(list(feats[a:b])), pa.array(ids[a:b])],
                ["features", "id"])]
            for a, b in bounds]
        serve_ingest = "arrow"
    except Exception:
        arrow_parts = row_parts
        serve_ingest = "rows"

    import tempfile as _tempfile

    tmpdir = _tempfile.mkdtemp(prefix="tfos_serving_")
    try:
        export_dir = os.path.join(tmpdir, "export")
        compat.export_saved_model({"params": {"w": w}}, export_dir)
        import jax

        predict = jax.jit(lambda p, b: {"score": b["features"] @ p["w"]})

        # two-bucket geometry: the small bucket catches ragged tails so
        # they don't pad (and waste forward compute) all the way up to
        # batch_size — the padding-waste/compile-count tradeoff buckets
        # exist for (serving_compiles_total == 2 == len(buckets))
        bucket_sizes = [max(1, batch_size // 4), batch_size]

        def runner(legacy: bool) -> "pipeline._RunModel":
            return pipeline._RunModel(
                export_dir=export_dir, model_name=None, predict_fn=predict,
                batch_size=batch_size,
                input_mapping={"features": "features"},
                output_mapping={"score": "score"},
                columns=["features", "id"], backend="sparkapi",
                bucket_sizes=bucket_sizes, legacy=legacy)

        def drive(rm, parts) -> list:
            out = []
            for part in parts:
                out.extend(rm(iter(part)))
            return out

        compiles = obs.counter(
            "serving_compiles_total",
            "distinct input-shape signatures handed to a serving forward "
            "(jit compilation keys)")
        bucketed, legacy = runner(False), runner(True)
        c0 = compiles.value
        warm_b = drive(bucketed, arrow_parts)  # compiles counted here
        serving_compiles = compiles.value - c0
        warm_l = drive(legacy, row_parts)
        got = np.asarray([r["score"] for r in warm_b])
        want = np.asarray([r["score"] for r in warm_l])
        if got.shape != want.shape or not np.allclose(got, want,
                                                      atol=1e-5):
            raise RuntimeError(
                "bucketed serving outputs diverge from the legacy row loop "
                f"(shapes {got.shape} vs {want.shape}) — refusing to stamp "
                "a throughput number for a wrong answer")

        def timed_once(rm, parts) -> float:
            t0 = time.perf_counter()
            n = len(drive(rm, parts))
            dt = time.perf_counter() - t0
            if n != rows_total:
                raise RuntimeError(
                    f"serving bench lost rows: {n}/{rows_total}")
            return dt

        # interleave the reps so ambient load on this shared container
        # hits both planes symmetrically; best-of-reps per plane.  The
        # flight recorder is reset here so its breakdown covers exactly
        # the timed bucketed reps (warm/equality passes excluded): the
        # additive consumer stages (wait/compute/emit) must sum to the
        # reps' combined wall within the gate tolerance
        from tensorflowonspark_tpu.obs import flight

        rec = flight.recorder("serve")
        rec.reset()
        recording = flight.enabled()

        def timed_unrecorded() -> float:
            with _flight_disabled():
                return timed_once(bucketed, arrow_parts)

        legacy_dts, serve_dts, off_dts = [], [], []
        for i in range(reps):
            legacy_dts.append(timed_once(legacy, row_parts))
            # recorder-overhead reps: the same bucketed pass with
            # TFOS_FLIGHT=0, interleaved (ambient drift hits on and off
            # symmetrically — an off-block AFTER all on-reps reads
            # container noise as recorder cost) AND order-alternated
            # (the second of two back-to-back bucketed passes runs
            # cache-warm; a fixed order would bias the comparison).
            # Skipped when the recorder is already opted out — nothing
            # to compare against.
            if not recording:
                serve_dts.append(timed_once(bucketed, arrow_parts))
            elif i % 2 == 0:
                serve_dts.append(timed_once(bucketed, arrow_parts))
                off_dts.append(timed_unrecorded())
            else:
                off_dts.append(timed_unrecorded())
                serve_dts.append(timed_once(bucketed, arrow_parts))
        legacy_rps = rows_total / min(legacy_dts)
        serve_rps = rows_total / min(serve_dts)
        out = {
            "serve_rows_per_sec": round(serve_rps, 1),
            "serve_rows_per_sec_legacy": round(legacy_rps, 1),
            "serve_speedup": round(serve_rps / legacy_rps, 2),
            "serve_ingest": serve_ingest,
            "serving_compiles_total": int(serving_compiles),
            "serve_rows_total": rows_total,
            "serve_batch_size": batch_size,
            "serve_row_bytes": int(feats[0].nbytes + 8),
            "serve_bucket_sizes": list(
                serving.resolve_buckets(batch_size, bucket_sizes)),
            "serve_partition_tails": [(b - a) % batch_size
                                      for a, b in bounds],
        }
        if recording:
            out["serve_stage_breakdown"] = rec.breakdown(sum(serve_dts))
            off_rps = rows_total / min(off_dts)
            out["serve_flight_overhead_frac"] = round(
                1.0 - serve_rps / off_rps, 4)
        else:
            # opted-out runs cannot decompose their wall: explicit null +
            # reason keeps the r09 schema total (gate-exempt)
            out["serve_stage_breakdown"] = None
            out["serve_stage_breakdown_reason"] = (
                "flight recorder disabled (TFOS_FLIGHT=0)")
        return out
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def measure_serving_online(clients: int = 32, reqs_per_client: int = 100,
                           feature_dim: int = 256, hidden_dim: int = 1024,
                           out_dim: int = 8, batch_size: int = 64,
                           flush_ms: float = 4.0,
                           slo_ms: float = 500.0,
                           deadline: "_Deadline | None" = None) -> dict:
    """Online-serving microbench: closed-loop rows/sec through the REAL
    coalescer → bucketed forward → scatter path, vs N independent
    single-request callers, at the same p99 SLO.

    ``clients`` threads each submit single-row requests back-to-back
    (closed loop — a new request only after the previous reply), once
    through a live :class:`tensorflowonspark_tpu.online.OnlineServer`
    (tenant warmed on load, bucket ladder ``[batch_size//4, batch_size]``,
    ``flush_ms`` deadline) and once as the uncoalesced baseline: the same
    threads calling the same jitted forward directly, one request per
    forward — what N independent callers sharing a process pay without a
    coalescing tier.  The forward is a CTR-serving-shaped MLP
    (``feature_dim → hidden_dim → out_dim``): heavy enough that a
    single-row call is real work (one vector-matrix pass per request, the
    per-request jit dispatch on top), which is exactly the regime
    coalescing exists for — one batch-N matrix-matrix forward amortizes
    both the dispatch and the memory traffic N single-row calls pay
    separately.  Every reply is checked against the precomputed expected
    outputs before either number is stamped, and both paths' p99 must
    meet ``slo_ms`` for the numbers to stand (a throughput claimed at an
    SLO it missed is not a measurement).  Any shed or dropped request
    fails the measurement into null + reason — the closed loop is sized
    inside the admission bound, so a shed here is a bug, not load.

    Host-side and CPU-capable like the other microbenches, so the number
    stays valid on accelerator-degraded rounds.  From r11 the artifact
    also carries ``online_stage_breakdown`` (the ``"online"`` flight
    plane: consumer ``wait``/``compute``/``reply`` reconciling with the
    measured wall, coalescer ``coalesce``/``pad`` overlapped beside it).
    From r12 it carries ``trace_overhead_frac``: request-scoped tracing
    measured by A/B — three traced closed loops strictly alternating
    with three under ``TFOS_TRACE_REQUESTS=0``; each adjacent (on, off)
    pair yields one ratio and the stamp is the MEDIAN of the pair ratios
    (paired comparison cancels the ambient drift that dominates walls on
    a shared box).  The headline ``online_rows_per_sec`` (and its
    p50/p99/SLO check and stage breakdown) all come from the FIRST
    traced pass — one pass, one self-consistent measurement; the extra
    passes exist only for the overhead A/B.
    """
    import shutil
    import tempfile as _tempfile
    import threading

    import numpy as np

    from tensorflowonspark_tpu import compat, online, serving
    from tensorflowonspark_tpu.obs import flight
    from tensorflowonspark_tpu.obs import trace as trace_lib

    rng = np.random.default_rng(0)
    w1 = (rng.standard_normal((feature_dim, hidden_dim))
          .astype(np.float32) * (2.0 / feature_dim) ** 0.5)
    w2 = (rng.standard_normal((hidden_dim, out_dim))
          .astype(np.float32) * (2.0 / hidden_dim) ** 0.5)
    params = {"w1": w1, "w2": w2}
    rows_total = clients * reqs_per_client
    feats = rng.standard_normal(
        (rows_total, feature_dim)).astype(np.float32)
    expected = np.maximum(feats @ w1, 0.0) @ w2
    # three-bucket ladder: continuous batching produces a spread of
    # coalesce sizes (arrival ÷ service rate), and a sparse ladder pads
    # most of them up to batch_size — compute spent on invented rows
    bucket_sizes = [max(1, batch_size // 4), max(1, batch_size // 2),
                    batch_size]

    tmpdir = _tempfile.mkdtemp(prefix="tfos_online_")
    srv = None
    try:
        export_dir = os.path.join(tmpdir, "export")
        compat.export_saved_model({"params": params}, export_dir)
        import jax

        predict = jax.jit(lambda p, b: {
            "score": jax.nn.relu(b["features"] @ p["w1"]) @ p["w2"]})
        srv = online.OnlineServer()
        tenant = srv.add_tenant(
            "bench", export_dir=export_dir, predict_fn=predict,
            batch_size=batch_size, bucket_sizes=bucket_sizes,
            flush_ms=flush_ms,
            warmup_example={"features": np.zeros(feature_dim,
                                                 np.float32)})
        srv.start()

        def closed_loop(call) -> tuple[float, list[float], list[str]]:
            """clients threads × reqs_per_client single-row requests;
            returns (wall_s, per-request latencies, errors)."""
            lats: list[list[float]] = [[] for _ in range(clients)]
            errs: list[str] = []

            def client(ci: int) -> None:
                base = ci * reqs_per_client
                try:
                    for k in range(reqs_per_client):
                        i = base + k
                        t0 = time.perf_counter()
                        out = call(feats[i:i + 1])
                        lats[ci].append(time.perf_counter() - t0)
                        if not np.allclose(out, expected[i:i + 1],
                                           atol=1e-5):
                            raise RuntimeError(
                                f"row {i}: output diverges from the "
                                "uncoalesced expectation")
                except Exception as e:
                    errs.append(f"client {ci}: {e!r}")

            threads = [threading.Thread(target=client, args=(ci,),
                                        daemon=True)
                       for ci in range(clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=240.0)
            wall = time.perf_counter() - t0
            if any(t.is_alive() for t in threads):
                errs.append("client thread(s) still alive after 240s — "
                            "wedged caller")
            return wall, [v for per in lats for v in per], errs

        def via_server(x):
            return srv.submit("bench", {"features": x}, timeout=60.0)[
                "score"]

        # the uncoalesced baseline: same forward, one request per call —
        # warm its (1, d) signature first so neither path pays a compile
        # inside the timed window (the coalesced tenant was warmed on load)
        np.asarray(predict(params, {"features": feats[:1]})["score"])

        def via_direct(x):
            return np.asarray(predict(params, {"features": x})["score"])

        # un-timed warm passes exercise both full paths once
        for call in (via_server, via_direct):
            call(feats[:1])

        rec = flight.recorder("online")
        shed_before = int(srv._shed_total.value)
        rec.reset()
        wall, lats, errs = closed_loop(via_server)
        if errs:
            raise RuntimeError("; ".join(errs[:3]))
        if len(lats) != rows_total:
            raise RuntimeError(
                f"lost replies: {len(lats)}/{rows_total}")
        breakdown = rec.breakdown(wall)
        p99 = float(np.percentile(lats, 99))
        p50 = float(np.percentile(lats, 50))

        # tracing-overhead A/B: the traced pass above is the first "on"
        # rep; each ADJACENT (on, off) pair yields one overhead ratio and
        # the stamped fraction is the MEDIAN of the pair ratios — paired
        # comparison cancels the ambient drift that dominates closed-loop
        # walls on a shared 2-core box (a same-config control pairing
        # measured a ±3% noise floor; best-of ratios inherit it, paired
        # medians mostly don't).
        def server_pass() -> float:
            w, ls, es = closed_loop(via_server)
            if es:
                raise RuntimeError("; ".join(es[:3]))
            if len(ls) != rows_total:
                raise RuntimeError(f"lost replies: {len(ls)}/{rows_total}")
            return w

        def out_of_budget() -> bool:
            # each remaining pass costs ~wall; stop the A/B (never the
            # whole bench) when the invocation budget is nearly spent
            return (deadline is not None
                    and deadline.remaining() < max(30.0, 4 * wall))

        on_walls, off_walls = [wall], []
        if trace_lib.requests_enabled():
            for _ in range(2):
                if out_of_budget():
                    break
                with _trace_requests_disabled():
                    off_walls.append(server_pass())
                on_walls.append(server_pass())
            if off_walls and not out_of_budget():
                with _trace_requests_disabled():
                    off_walls.append(server_pass())
        shed = int(srv._shed_total.value) - shed_before
        if shed:
            raise RuntimeError(
                f"{shed} request(s) shed during a closed loop sized "
                "inside the admission bound — refusing to stamp")

        uwall, ulats, uerrs = closed_loop(via_direct)
        if uerrs:
            raise RuntimeError("; ".join(uerrs[:3]))
        up99 = float(np.percentile(ulats, 99))
        for name, val in (("coalesced", p99), ("uncoalesced", up99)):
            if val * 1000 > slo_ms:
                raise RuntimeError(
                    f"{name} p99 {val * 1000:.1f}ms misses the "
                    f"{slo_ms}ms SLO — a rows/sec claimed at an SLO it "
                    "missed is not a measurement")

        # headline from the FIRST traced pass only: its p99 was measured
        # and SLO-checked; a faster later pass whose tail was never
        # examined must not become the claimed number
        rps = rows_total / wall
        urps = rows_total / uwall
        return {
            "online_rows_per_sec": round(rps, 1),
            "online_rows_per_sec_uncoalesced": round(urps, 1),
            "online_speedup": round(rps / urps, 2),
            "online_p50_ms": round(p50 * 1000, 3),
            "online_p99_ms": round(p99 * 1000, 3),
            "online_p99_ms_uncoalesced": round(up99 * 1000, 3),
            "online_slo_ms": slo_ms,
            "online_clients": clients,
            "online_rows_total": rows_total,
            "online_batch_size": batch_size,
            "online_feature_dim": feature_dim,
            "online_hidden_dim": hidden_dim,
            "online_flush_ms": flush_ms,
            "online_bucket_sizes": list(
                serving.resolve_buckets(batch_size, bucket_sizes)),
            "online_shed_total": shed,
            "online_coalesce_p50_rows": _hist_quantile_rows(
                srv._coalesce_size, 0.50),
            "online_stage_breakdown": (breakdown if flight.enabled()
                                       else None),
            **({} if flight.enabled() else {
                "online_stage_breakdown_reason":
                    "flight recorder disabled (TFOS_FLIGHT=0)"}),
            "trace_overhead_frac": (
                round(statistics.median(
                    1.0 - off_w / on_w
                    for on_w, off_w in zip(on_walls, off_walls)), 4)
                if off_walls else None),
            **({} if off_walls else {
                "trace_overhead_reason":
                    ("request tracing disabled (TFOS_TRACE_REQUESTS=0) — "
                     "no traced side to A/B"
                     if not trace_lib.requests_enabled() else
                     "wall budget exhausted before the tracing A/B")}),
            "online_tenant_p99_ms": tenant.quantile_ms(0.99),
        }
    finally:
        if srv is not None:
            srv.stop()
        shutil.rmtree(tmpdir, ignore_errors=True)


def measure_serving_decode(clients: int = 6, reqs_per_client: int = 6,
                           max_new_tokens: int = 24,
                           prompt_len_lo: int = 8, prompt_len_hi: int = 24,
                           max_seqs: int = 8, page_size: int = 8,
                           ttft_slo_ms: float = 5000.0,
                           itl_slo_ms: float = 1000.0,
                           deadline: "_Deadline | None" = None) -> dict:
    """Generative-decode microbench: closed-loop aggregate tokens/sec
    through the REAL continuous-batching engine (admit/retire between
    decode steps, paged KV pool) vs sequential per-request decode.

    ``clients`` threads each run ``reqs_per_client`` generations
    back-to-back (closed loop) against one live
    :class:`tensorflowonspark_tpu.decode.DecodeEngine` — varied prompt
    lengths (the ladder exercises more than one prefill bucket), greedy
    decoding, tokens consumed as they stream.  The BASELINE is the same
    requests run strictly one at a time through the same engine: same
    jitted prefill/decode executables, same pool — isolating exactly the
    scheduling claim (a decode step over S active slots costs ~one slot's
    step on a dispatch-bound box, so interleaving S sequences multiplies
    tokens per step-wall).  The baseline runs LAST so ambient drift (a
    box warming up) biases against the claim.

    Refused-to-stamp conditions: any token-level output mismatch between
    the concurrent and sequential passes (``decode_output_equality:
    "fail"`` + null numbers — the gate fails the artifact), a TTFT or
    inter-token p99 over its SLO, any shed during a loop sized inside
    the admission bound, leaked KV pages after either pass, or any jit
    signature minted after warmup (the zero-new-signatures invariant —
    a decode that recompiles mid-stream is the failure mode this tier
    exists to prevent).

    Host-side and CPU-capable like the other microbenches.  Also stamps
    the ``"decode"`` flight plane's stage breakdown (``wait`` /
    ``prefill`` / ``decode`` reconciling with the concurrent wall) and
    the peak KV-pool occupancy.
    """
    import threading

    import jax
    import numpy as np

    from tensorflowonspark_tpu import decode as decode_lib
    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.models import tinylm
    from tensorflowonspark_tpu.obs import flight

    config = tinylm.Config.tiny()
    engine = decode_lib.DecodeEngine(
        config, max_seqs=max_seqs, page_size=page_size,
        max_len=config.max_len, max_prompt_len=prompt_len_hi,
        ttft_slo_ms=ttft_slo_ms, itl_slo_ms=itl_slo_ms)
    try:
        engine.warmup()
        engine.start()
        enumerated = set(engine.enumerate_signatures())
        n = clients * reqs_per_client
        rng = np.random.default_rng(7)
        lengths = [prompt_len_lo
                   + int(i * (prompt_len_hi - prompt_len_lo)
                         / max(1, n - 1)) for i in range(n)]
        prompts = [rng.integers(0, config.vocab_size, size=(ln,)
                                ).astype(np.int32) for ln in lengths]

        def run_one(i: int) -> tuple[list[int], float, list[float]]:
            t0 = time.perf_counter()
            toks: list[int] = []
            times: list[float] = []
            for tok in engine.submit(prompts[i],
                                     max_new_tokens=max_new_tokens
                                     ).tokens(timeout=120.0):
                toks.append(tok)
                times.append(time.perf_counter())
            ttft = times[0] - t0 if times else float("inf")
            itls = [b - a for a, b in zip(times, times[1:])]
            return toks, ttft, itls

        shed_before = int(engine._shed_total.value)
        rec = flight.recorder("decode")
        rec.reset()

        # concurrent pass FIRST (the baseline runs last so drift biases
        # against the speedup claim)
        conc: list = [None] * n
        errs: list[str] = []

        def client(ci: int) -> None:
            try:
                for k in range(reqs_per_client):
                    i = ci * reqs_per_client + k
                    conc[i] = run_one(i)
            except Exception as e:
                errs.append(f"client {ci}: {e!r}")

        threads = [threading.Thread(target=client, args=(ci,), daemon=True)
                   for ci in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300.0)
        wall = time.perf_counter() - t0
        if errs or any(t.is_alive() for t in threads):
            raise RuntimeError("; ".join(errs[:3]) or
                               "client thread(s) wedged past 300s")
        breakdown = rec.breakdown(wall)
        # the prefix registry legitimately pins its registered pages
        # until eviction or stop — only pages beyond that set leaked
        pinned = (engine._registry.pinned_pages
                  if engine._registry is not None else 0)
        if engine.pool.used_pages != pinned:
            raise RuntimeError(
                f"{engine.pool.used_pages - pinned} KV pages leaked "
                "after the concurrent pass")
        shed = int(engine._shed_total.value) - shed_before
        if shed:
            raise RuntimeError(
                f"{shed} request(s) shed during a closed loop sized "
                "inside the admission bound — refusing to stamp")
        peak_occupancy = round(
            engine.pool.peak_used / (engine.num_pages - 1), 4)

        ident = {
            "decode_clients": clients,
            "decode_requests": n,
            "decode_max_new_tokens": max_new_tokens,
            "decode_prompt_lens": [prompt_len_lo, prompt_len_hi],
            "decode_model": (f"tiny_lm_d{config.dim}"
                             f"L{config.n_layers}H{config.n_heads}"
                             f"v{config.vocab_size}"),
            "decode_page_size": page_size,
            "decode_max_seqs": max_seqs,
            "decode_num_pages": engine.num_pages,
            "decode_prefill_buckets": list(engine.prefill_buckets),
            "decode_ttft_slo_ms": ttft_slo_ms,
            "decode_itl_slo_ms": itl_slo_ms,
            "decode_devices": len(jax.devices()),
            "decode_host_cpus": os.cpu_count(),
        }

        # sequential baseline: the same requests, one at a time, through
        # the same engine (same executables, same pool).  Budget check
        # first (the sibling microbenches' discipline): the baseline
        # costs ~max_seqs× the concurrent wall, and a half-measured A/B
        # stamped late delays every stamp after it
        if deadline is not None \
                and deadline.remaining() < max(30.0, 2 * max_seqs * wall):
            return {
                "decode_tokens_per_sec": None,
                "decode_reason": (
                    "wall budget exhausted after the concurrent pass "
                    f"({deadline.remaining():.0f}s left); sequential "
                    "baseline unmeasured"),
                **ident,
            }
        t0 = time.perf_counter()
        seq = [run_one(i) for i in range(n)]
        uwall = time.perf_counter() - t0
        pinned = (engine._registry.pinned_pages
                  if engine._registry is not None else 0)
        if engine.pool.used_pages != pinned:
            raise RuntimeError(
                f"{engine.pool.used_pages - pinned} KV pages leaked "
                "after the sequential pass")

        seen = serving._SEEN_SHAPES.get(engine.cache_key, set())
        if seen != enumerated:
            raise RuntimeError(
                f"steady-state decode minted {len(seen - enumerated)} jit "
                "signature(s) beyond the warmup enumeration — sequence "
                "growth is recompiling")

        if [t for t, _, _ in conc] != [t for t, _, _ in seq]:
            bad = sum(1 for a, b in zip(conc, seq) if a[0] != b[0])
            return {
                "decode_tokens_per_sec": None,
                "decode_output_equality": "fail",
                "decode_reason": (
                    f"{bad}/{n} request(s) decoded different tokens "
                    "concurrently vs sequentially: broken, not fast"),
                **ident,
            }
        total_tokens = sum(len(t) for t, _, _ in conc)
        ttfts = [ttft for _, ttft, _ in conc]
        itls = [g for _, _, gs in conc for g in gs]
        ttft_p99 = float(np.percentile(ttfts, 99)) * 1000
        itl_p99 = (float(np.percentile(itls, 99)) * 1000 if itls else 0.0)
        for name, p99, slo in (("TTFT", ttft_p99, ttft_slo_ms),
                               ("inter-token", itl_p99, itl_slo_ms)):
            if p99 > slo:
                raise RuntimeError(
                    f"{name} p99 {p99:.1f}ms misses the {slo}ms SLO — a "
                    "tokens/sec claimed at an SLO it missed is not a "
                    "measurement")
        tps = total_tokens / wall
        utps = total_tokens / uwall
        return {
            "decode_tokens_per_sec": round(tps, 1),
            "decode_tokens_per_sec_sequential": round(utps, 1),
            "decode_speedup": round(tps / utps, 2),
            "decode_output_equality": "pass",
            "decode_tokens_total": total_tokens,
            "decode_ttft_ms_p50": round(
                float(np.percentile(ttfts, 50)) * 1000, 3),
            "decode_ttft_ms_p99": round(ttft_p99, 3),
            "decode_itl_ms_p50": round(
                (float(np.percentile(itls, 50)) * 1000 if itls else 0.0),
                3),
            "decode_itl_ms_p99": round(itl_p99, 3),
            "decode_kv_occupancy_peak": peak_occupancy,
            "decode_stage_breakdown": (breakdown if flight.enabled()
                                       else None),
            **({} if flight.enabled() else {
                "decode_stage_breakdown_reason":
                    "flight recorder disabled (TFOS_FLIGHT=0)"}),
            **ident,
        }
    finally:
        engine.stop()


def measure_decode_prefill(clients: int = 8, reqs_per_client: int = 4,
                           max_new_tokens: int = 12,
                           short_len: int = 4, long_len: int = 24,
                           prefix_len: int = 20, shared_reqs: int = 8,
                           max_seqs: int = 8, page_size: int = 8,
                           prefill_chunk: int = 8,
                           ttft_slo_ms: float = 5000.0,
                           itl_slo_ms: float = 1000.0,
                           deadline: "_Deadline | None" = None) -> dict:
    """Chunked-prefill + COW prefix-sharing microbench (ISSUE 19).

    Two claims, measured against the LEGACY per-prompt-prefill engine
    (``prefill_chunk=0`` — same model, same pool geometry, same decode
    step) as the baseline:

    - **Short-prompt TTFT under mixed load**: a closed loop of
      interleaved short and long prompts.  Legacy prefill runs a whole
      long prompt in one engine step while admitted short prompts wait;
      chunked prefill advances every prefilling slot at most
      ``prefill_chunk`` tokens per step in ONE fixed-shape call, so a
      short prompt's first token is bounded by the chunk budget, not by
      its neighbours' prompt lengths.  Stamped as the short-prompt TTFT
      p99 both ways.
    - **Sub-linear unique pages for shared prefixes**: ``shared_reqs``
      sequential requests sharing a ``prefix_len``-token prefix.  The
      chunked engine's prefix registry maps the common pages refcounted
      read-only (COW on divergence), so cumulative page allocation
      grows sub-linearly in N while the legacy engine pays full price
      per request.  Stamped as the allocated-page counts both ways.

    Refused-to-stamp conditions follow ``measure_serving_decode``: any
    token-level mismatch between the chunked and legacy engines (the
    sharing/chunking must be exact, not approximately right), any shed
    inside the admission bound, leaked pages or a violated pool
    invariant after any pass, any jit signature minted after warmup.
    The baseline engine runs LAST so ambient drift biases against the
    claim; an exhausted wall budget before it stamps null + reason.
    Host-side and CPU-capable; COW/sharing counters and the
    ``prefill_chunk`` flight stage breakdown ride along.
    """
    import threading

    import jax
    import numpy as np

    from tensorflowonspark_tpu import decode as decode_lib
    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.models import tinylm
    from tensorflowonspark_tpu.obs import flight

    config = tinylm.Config.tiny()
    n = clients * reqs_per_client
    rng = np.random.default_rng(19)
    # interleaved short/long mix: even indices short, odd long — every
    # client thread carries both classes, so short TTFTs are measured
    # while long prefills genuinely compete for the engine loop
    lengths = [short_len if i % 2 == 0 else long_len for i in range(n)]
    prompts = [rng.integers(0, config.vocab_size, size=(ln,)
                            ).astype(np.int32) for ln in lengths]
    prefix = rng.integers(0, config.vocab_size,
                          size=(prefix_len,)).astype(np.int32)
    shared_prompts = [np.concatenate([
        prefix, rng.integers(0, config.vocab_size, size=(4,))]
    ).astype(np.int32) for _ in range(shared_reqs)]

    def _run_engine(chunk: int) -> dict:
        engine = decode_lib.DecodeEngine(
            config, max_seqs=max_seqs, page_size=page_size,
            max_len=config.max_len, max_prompt_len=long_len,
            ttft_slo_ms=ttft_slo_ms, itl_slo_ms=itl_slo_ms,
            prefill_chunk=chunk)
        try:
            engine.warmup()
            engine.start()
            enumerated = set(engine.enumerate_signatures())
            shed_before = int(engine._shed_total.value)
            rec = flight.recorder("decode")
            rec.reset()

            def run_one(i: int):
                t0 = time.perf_counter()
                toks, times = [], []
                for tok in engine.submit(
                        prompts[i], max_new_tokens=max_new_tokens
                        ).tokens(timeout=120.0):
                    toks.append(tok)
                    times.append(time.perf_counter())
                ttft = times[0] - t0 if times else float("inf")
                itls = [b - a for a, b in zip(times, times[1:])]
                return toks, ttft, itls

            out: list = [None] * n
            errs: list[str] = []

            def client(ci: int) -> None:
                try:
                    for k in range(reqs_per_client):
                        i = ci * reqs_per_client + k
                        out[i] = run_one(i)
                except Exception as e:
                    errs.append(f"client {ci}: {e!r}")

            threads = [threading.Thread(target=client, args=(ci,),
                                        daemon=True)
                       for ci in range(clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300.0)
            wall = time.perf_counter() - t0
            if errs or any(t.is_alive() for t in threads):
                raise RuntimeError("; ".join(errs[:3]) or
                                   "client thread(s) wedged past 300s")
            breakdown = rec.breakdown(wall)
            # sequential shared-prefix phase: registry hits require the
            # registering request to COMPLETE first, so back-to-back
            # submission is the honest sharing workload
            alloc0 = engine.pool.alloc_total
            shared_out = [
                list(engine.submit(p, max_new_tokens=4).result())
                for p in shared_prompts]
            alloc_pages = engine.pool.alloc_total - alloc0
            kv = engine.stats()["admission"]["kv"]
            if not kv["invariant"]["ok"]:
                raise RuntimeError(
                    f"pool invariant violated: {kv['invariant']}")
            # the prefix registry legitimately pins registered pages
            # until eviction/stop; anything beyond that is a leak
            pinned = (engine._registry.pinned_pages
                      if engine._registry is not None else 0)
            if engine.pool.used_pages != pinned:
                raise RuntimeError(
                    f"{engine.pool.used_pages - pinned} KV pages leaked")
            shed = int(engine._shed_total.value) - shed_before
            if shed:
                raise RuntimeError(
                    f"{shed} request(s) shed inside the admission bound "
                    "— refusing to stamp")
            seen = serving._SEEN_SHAPES.get(engine.cache_key, set())
            if seen != enumerated:
                raise RuntimeError(
                    f"minted {len(seen - enumerated)} jit signature(s) "
                    "beyond the warmup enumeration")
            short_ttfts = [t for i, (_, t, _) in enumerate(out)
                           if lengths[i] == short_len]
            itls = [g for _, _, gs in out for g in gs]
            return {
                "tokens": [t for t, _, _ in out],
                "shared_tokens": shared_out,
                "wall": wall,
                "total_tokens": sum(len(t) for t, _, _ in out),
                "short_ttft_p50": float(np.percentile(short_ttfts, 50)),
                "short_ttft_p99": float(np.percentile(short_ttfts, 99)),
                "itl_p99": (float(np.percentile(itls, 99))
                            if itls else 0.0),
                "alloc_pages": int(alloc_pages),
                "prefix_hits": int(kv["prefix_hits_total"]),
                "shared_pages_total": int(kv["shared_pages_total"]),
                "cow_copies": int(kv["cow_copies_total"]),
                "breakdown": breakdown,
                "peak_occupancy": round(
                    engine.pool.peak_used / (engine.num_pages - 1), 4),
                "chunks": list(engine.prefill_chunks),
            }
        finally:
            engine.stop()
            engine.pool.check_invariant()

    chunked = _run_engine(prefill_chunk)
    ident = {
        "decode_prefill_clients": clients,
        "decode_prefill_requests": n,
        "decode_prefill_shared_requests": shared_reqs,
        "decode_prefill_max_new_tokens": max_new_tokens,
        "decode_prefill_prompt_lens": [short_len, long_len],
        "decode_prefill_prefix_len": prefix_len,
        "decode_prefill_chunk": prefill_chunk,
        "decode_prefill_chunks": chunked["chunks"],
        "decode_prefill_model": (f"tiny_lm_d{config.dim}"
                                 f"L{config.n_layers}H{config.n_heads}"
                                 f"v{config.vocab_size}"),
        "decode_prefill_page_size": page_size,
        "decode_prefill_max_seqs": max_seqs,
        "decode_prefill_devices": len(jax.devices()),
        "decode_prefill_host_cpus": os.cpu_count(),
    }
    stamped = {
        "decode_prefill_tokens_per_sec": round(
            chunked["total_tokens"] / chunked["wall"], 1),
        "decode_prefill_short_ttft_ms_p50": round(
            chunked["short_ttft_p50"] * 1000, 3),
        "decode_prefill_short_ttft_ms_p99": round(
            chunked["short_ttft_p99"] * 1000, 3),
        "decode_prefill_alloc_pages": chunked["alloc_pages"],
        "decode_prefill_prefix_hits": chunked["prefix_hits"],
        "decode_prefill_shared_pages_total": chunked["shared_pages_total"],
        "decode_prefill_cow_copies": chunked["cow_copies"],
        "decode_prefill_kv_occupancy_peak": chunked["peak_occupancy"],
        "decode_prefill_stage_breakdown": (
            chunked["breakdown"] if flight.enabled() else None),
        **({} if flight.enabled() else {
            "decode_prefill_stage_breakdown_reason":
                "flight recorder disabled (TFOS_FLIGHT=0)"}),
        **ident,
    }
    for name, p99, slo in (
            ("short-prompt TTFT", chunked["short_ttft_p99"] * 1000,
             ttft_slo_ms),
            ("inter-token", chunked["itl_p99"] * 1000, itl_slo_ms)):
        if p99 > slo:
            raise RuntimeError(
                f"{name} p99 {p99:.1f}ms misses the {slo}ms SLO — a "
                "number claimed at an SLO it missed is not a measurement")
    # baseline LAST (drift bias against the claim), budget-checked first
    if deadline is not None \
            and deadline.remaining() < max(30.0, 2 * chunked["wall"]):
        return {
            "decode_prefill_short_ttft_speedup": None,
            "decode_prefill_reason": (
                "wall budget exhausted after the chunked pass "
                f"({deadline.remaining():.0f}s left); per-prompt "
                "baseline unmeasured"),
            **stamped,
        }
    legacy = _run_engine(0)
    if (chunked["tokens"] != legacy["tokens"]
            or chunked["shared_tokens"] != legacy["shared_tokens"]):
        bad = sum(1 for a, b in zip(
            chunked["tokens"] + chunked["shared_tokens"],
            legacy["tokens"] + legacy["shared_tokens"]) if a != b)
        return {
            "decode_prefill_short_ttft_ms_p99": None,
            "decode_prefill_short_ttft_speedup": None,
            "decode_prefill_output_equality": "fail",
            "decode_prefill_reason": (
                f"{bad} request(s) decoded different tokens chunked vs "
                "per-prompt: broken, not fast"),
            **ident,
        }
    if chunked["alloc_pages"] >= legacy["alloc_pages"]:
        raise RuntimeError(
            f"prefix sharing allocated {chunked['alloc_pages']} pages vs "
            f"{legacy['alloc_pages']} per-prompt — the sub-linear claim "
            "failed on this box")
    speedup = (round(legacy["short_ttft_p99"] / chunked["short_ttft_p99"],
                     2)
               if chunked["short_ttft_p99"] > 0 else None)
    extra = {}
    if speedup is not None and speedup < 1.0 \
            and len(jax.devices()) == 1:
        # a compute-bound single-device host pays real FLOPs for the
        # fixed (max_seqs, chunk) geometry that a dispatch-bound
        # accelerator gets for ~one slot's cost — the TTFT claim is not
        # measurable here; the sharing/equality claims above still are
        extra["decode_prefill_short_ttft_speedup_reason"] = (
            "compute-bound single-device host: the packed fixed-shape "
            "prefill call costs more FLOPs than per-prompt calls; the "
            "TTFT claim needs a dispatch-bound accelerator")
        speedup = None
    return {
        **stamped,
        "decode_prefill_output_equality": "pass",
        "decode_prefill_short_ttft_ms_p99_baseline": round(
            legacy["short_ttft_p99"] * 1000, 3),
        "decode_prefill_short_ttft_speedup": speedup,
        **extra,
        "decode_prefill_tokens_per_sec_baseline": round(
            legacy["total_tokens"] / legacy["wall"], 1),
        "decode_prefill_alloc_pages_baseline": legacy["alloc_pages"],
        "decode_prefill_page_savings_frac": round(
            1.0 - chunked["alloc_pages"] / legacy["alloc_pages"], 4),
    }


def measure_decode_spec(clients: int = 6, reqs_per_client: int = 4,
                        max_new_tokens: int = 24,
                        short_len: int = 4, long_len: int = 20,
                        prefix_len: int = 16, shared_reqs: int = 6,
                        spec_tokens: int = 4, spec_drafter: str = "ngram",
                        max_seqs: int = 8, page_size: int = 8,
                        prefill_chunk: int = 8,
                        ttft_slo_ms: float = 5000.0,
                        itl_slo_ms: float = 1000.0,
                        deadline: "_Deadline | None" = None) -> dict:
    """Speculative multi-token decoding microbench (ISSUE 20).

    The claim, measured against the SINGLE-TOKEN decode engine
    (``spec_tokens=0`` — same model, same pool geometry, same chunked
    prefill) as the baseline: a speculative engine (n-gram drafter,
    ``k`` drafts verified in ONE fixed-shape call per step) emits
    token streams IDENTICAL to the baseline under greedy selection
    while emitting MORE than one token per engine step — stamped as

    - ``spec_itl_p99_ratio``: speculative ITL p99 / baseline ITL p99,
      LOWER is better (the per-token latency the caller feels);
    - ``spec_tokens_per_step``: tokens emitted per verify step (the
      mechanism — >1 means accepted drafts collapsed engine steps);
    - ``spec_acceptance_rate``: the drafter's windowed hit rate.

    ``spec_itl_speedup`` (baseline/spec, higher better) stamps numeric
    only when speculation actually won the latency race; on a
    compute-bound single-device host the verify call's (k+1)-position
    FLOPs can cost more than the steps it saves, stamping null +
    ``spec_itl_speedup_reason`` — the equality and tokens-per-step
    claims still hold and still gate.

    Refused-to-stamp conditions follow ``measure_decode_prefill``: any
    token-level mismatch spec vs baseline (speculation must be exact,
    not approximately right), any shed inside the admission bound,
    leaked pages beyond the registry's pins, a violated pool invariant,
    any jit signature minted after warmup.  The baseline engine runs
    LAST so ambient drift biases against the claim; an exhausted wall
    budget before it stamps null + reason.  Host-side and CPU-capable;
    the speculate/verify flight-stage split rides along.
    """
    import threading

    import jax
    import numpy as np

    from tensorflowonspark_tpu import decode as decode_lib
    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.models import tinylm
    from tensorflowonspark_tpu.obs import flight

    config = tinylm.Config.tiny()
    n = clients * reqs_per_client
    rng = np.random.default_rng(20)
    # mixed short/long prompts with a LONG generation budget: tiny
    # greedy models settle into repeated-token cycles a few tokens in,
    # which is exactly the regime prompt-lookup drafting reads
    lengths = [short_len if i % 2 == 0 else long_len for i in range(n)]
    prompts = [rng.integers(0, config.vocab_size, size=(ln,)
                            ).astype(np.int32) for ln in lengths]
    prefix = rng.integers(0, config.vocab_size,
                          size=(prefix_len,)).astype(np.int32)
    shared_prompts = [np.concatenate([
        prefix, rng.integers(0, config.vocab_size, size=(4,))]
    ).astype(np.int32) for _ in range(shared_reqs)]

    def _run_engine(spec: int) -> dict:
        engine = decode_lib.DecodeEngine(
            config, max_seqs=max_seqs, page_size=page_size,
            max_len=config.max_len, max_prompt_len=long_len,
            ttft_slo_ms=ttft_slo_ms, itl_slo_ms=itl_slo_ms,
            prefill_chunk=prefill_chunk, spec_tokens=spec,
            spec_drafter=spec_drafter)
        try:
            engine.warmup()
            engine.start()
            enumerated = set(engine.enumerate_signatures())
            shed_before = int(engine._shed_total.value)
            steps0 = int(engine._spec_steps_total.value)
            emitted0 = int(engine._spec_emitted_total.value)
            rec = flight.recorder("decode")
            rec.reset()

            def run_one(i: int):
                t0 = time.perf_counter()
                toks, times = [], []
                for tok in engine.submit(
                        prompts[i], max_new_tokens=max_new_tokens
                        ).tokens(timeout=120.0):
                    toks.append(tok)
                    times.append(time.perf_counter())
                ttft = times[0] - t0 if times else float("inf")
                itls = [b - a for a, b in zip(times, times[1:])]
                return toks, ttft, itls

            out: list = [None] * n
            errs: list[str] = []

            def client(ci: int) -> None:
                try:
                    for k in range(reqs_per_client):
                        i = ci * reqs_per_client + k
                        out[i] = run_one(i)
                except Exception as e:
                    errs.append(f"client {ci}: {e!r}")

            threads = [threading.Thread(target=client, args=(ci,),
                                        daemon=True)
                       for ci in range(clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300.0)
            wall = time.perf_counter() - t0
            if errs or any(t.is_alive() for t in threads):
                raise RuntimeError("; ".join(errs[:3]) or
                                   "client thread(s) wedged past 300s")
            breakdown = rec.breakdown(wall)
            # sequential shared-prefix phase: speculation must compose
            # with registry hits, COW, and shared-page rollback safety
            shared_out = [
                list(engine.submit(p, max_new_tokens=8).result())
                for p in shared_prompts]
            kv = engine.stats()["admission"]["kv"]
            if not kv["invariant"]["ok"]:
                raise RuntimeError(
                    f"pool invariant violated: {kv['invariant']}")
            pinned = (engine._registry.pinned_pages
                      if engine._registry is not None else 0)
            if engine.pool.used_pages != pinned:
                raise RuntimeError(
                    f"{engine.pool.used_pages - pinned} KV pages leaked")
            shed = int(engine._shed_total.value) - shed_before
            if shed:
                raise RuntimeError(
                    f"{shed} request(s) shed inside the admission bound "
                    "— refusing to stamp")
            seen = serving._SEEN_SHAPES.get(engine.cache_key, set())
            if seen != enumerated:
                raise RuntimeError(
                    f"minted {len(seen - enumerated)} jit signature(s) "
                    "beyond the warmup enumeration")
            itls = [g for _, _, gs in out for g in gs]
            steps = int(engine._spec_steps_total.value) - steps0
            emitted = int(engine._spec_emitted_total.value) - emitted0
            return {
                "tokens": [t for t, _, _ in out],
                "shared_tokens": shared_out,
                "wall": wall,
                "total_tokens": sum(len(t) for t, _, _ in out),
                "itl_p50": (float(np.percentile(itls, 50))
                            if itls else 0.0),
                "itl_p99": (float(np.percentile(itls, 99))
                            if itls else 0.0),
                "steps": steps,
                "emitted": emitted,
                "acceptance": kv["spec_acceptance_rate"],
                "breakdown": breakdown,
                "ladder": list(engine.spec_ladder),
                "spec_k": kv["spec_k"],
            }
        finally:
            engine.stop()
            engine.pool.check_invariant()

    spec = _run_engine(spec_tokens)
    if spec["steps"] <= 0:
        raise RuntimeError("speculative engine ran zero verify steps — "
                           "the workload never reached the decode phase")
    tokens_per_step = round(spec["emitted"] / spec["steps"], 3)
    ident = {
        "spec_clients": clients,
        "spec_requests": n,
        "spec_shared_requests": shared_reqs,
        "spec_max_new_tokens": max_new_tokens,
        "spec_prompt_lens": [short_len, long_len],
        "spec_prefix_len": prefix_len,
        "spec_k": spec_tokens,
        "spec_drafter": spec_drafter,
        "spec_ladder": spec["ladder"],
        "spec_model": (f"tiny_lm_d{config.dim}"
                       f"L{config.n_layers}H{config.n_heads}"
                       f"v{config.vocab_size}"),
        "spec_page_size": page_size,
        "spec_max_seqs": max_seqs,
        "spec_prefill_chunk": prefill_chunk,
        "spec_devices": len(jax.devices()),
        "spec_host_cpus": os.cpu_count(),
    }
    stamped = {
        "spec_tokens_per_step": tokens_per_step,
        "spec_acceptance_rate": spec["acceptance"],
        "spec_tokens_per_sec": round(
            spec["total_tokens"] / spec["wall"], 1),
        "spec_itl_ms_p50": round(spec["itl_p50"] * 1000, 3),
        "spec_itl_ms_p99": round(spec["itl_p99"] * 1000, 3),
        "decode_spec_stage_breakdown": (
            spec["breakdown"] if flight.enabled() else None),
        **({} if flight.enabled() else {
            "decode_spec_stage_breakdown_reason":
                "flight recorder disabled (TFOS_FLIGHT=0)"}),
        **ident,
    }
    if spec["itl_p99"] * 1000 > itl_slo_ms:
        raise RuntimeError(
            f"speculative ITL p99 {spec['itl_p99'] * 1000:.1f}ms misses "
            f"the {itl_slo_ms}ms SLO — a number claimed at an SLO it "
            "missed is not a measurement")
    # baseline LAST (drift bias against the claim), budget-checked first
    if deadline is not None \
            and deadline.remaining() < max(30.0, 2 * spec["wall"]):
        return {
            "spec_itl_p99_ratio": None,
            "spec_reason": (
                "wall budget exhausted after the speculative pass "
                f"({deadline.remaining():.0f}s left); single-token "
                "baseline unmeasured"),
            **stamped,
        }
    base = _run_engine(0)
    if (spec["tokens"] != base["tokens"]
            or spec["shared_tokens"] != base["shared_tokens"]):
        bad = sum(1 for a, b in zip(
            spec["tokens"] + spec["shared_tokens"],
            base["tokens"] + base["shared_tokens"]) if a != b)
        return {
            "spec_itl_p99_ratio": None,
            "spec_itl_speedup": None,
            "decode_spec_output_equality": "fail",
            "spec_reason": (
                f"{bad} request(s) decoded different tokens speculative "
                "vs single-token: broken, not fast"),
            **ident,
        }
    if tokens_per_step <= 1.0:
        raise RuntimeError(
            f"speculation emitted {tokens_per_step} tokens/step — the "
            "drafter accepted nothing on this workload; refusing to "
            "stamp a speculative claim that never speculated")
    ratio = (round(spec["itl_p99"] / base["itl_p99"], 3)
             if base["itl_p99"] > 0 else None)
    speedup = (round(base["itl_p99"] / spec["itl_p99"], 2)
               if ratio is not None and spec["itl_p99"] > 0 else None)
    extra = {}
    if speedup is not None and speedup < 1.0 \
            and len(jax.devices()) == 1:
        # a compute-bound single-device host pays the verify call's
        # (k+1)-position FLOPs in full, where a dispatch-bound
        # accelerator gets the extra positions for ~one step's cost —
        # the latency claim is not measurable here; the equality and
        # tokens-per-step claims above still are
        extra["spec_itl_speedup_reason"] = (
            "compute-bound single-device host: the (k+1)-position "
            "verify call costs more FLOPs than the steps it collapses; "
            "the ITL claim needs a dispatch-bound accelerator")
        speedup = None
    return {
        **stamped,
        "decode_spec_output_equality": "pass",
        "spec_itl_p99_ratio": ratio,
        "spec_itl_speedup": speedup,
        **extra,
        "spec_itl_ms_p99_baseline": round(base["itl_p99"] * 1000, 3),
        "spec_tokens_per_sec_baseline": round(
            base["total_tokens"] / base["wall"], 1),
    }


def measure_serving_mesh(replicas: int = 3, clients: int = 16,
                         reqs_per_client: int = 40,
                         feature_dim: int = 256, hidden_dim: int = 1024,
                         out_dim: int = 8, batch_size: int = 64,
                         flush_ms: float = 4.0, slo_ms: float = 500.0,
                         kill_replica: bool = True,
                         deadline: "_Deadline | None" = None) -> dict:
    """Serving-mesh microbench: aggregate closed-loop rows/sec through
    the REAL registry → placement → router → replica-coalescer path with
    ``replicas`` separate server PROCESSES on this box, vs the
    single-process r11 baseline (the same workload through one in-process
    ``OnlineServer``).

    Phases:

    1. **Baseline** — one in-process ``OnlineServer`` hosts all
       ``replicas`` tenants; ``clients`` closed-loop threads submit
       single-row requests directly (the r11-measured path, no HTTP) →
       ``mesh_rows_per_sec_single_process``.
    2. **Mesh** — ``replicas`` subprocesses (each a full replica:
       ``python -m tensorflowonspark_tpu.mesh``), one tenant placed per
       replica (distinct exports — same-key co-location is covered by
       tests; the bench spreads load), the SAME client threads routed
       through ``MeshRouter.route_predict`` → ``mesh_rows_per_sec``,
       ``mesh_scale_efficiency`` = mesh / (replicas × baseline),
       ``mesh_speedup_vs_single_process`` = mesh / baseline.  Every
       reply is output-checked; any shed / lost reply / wedged caller
       fails the measurement into null + reason; both paths' p99 must
       meet ``slo_ms``.
    3. **Router hop** — sequential single-row requests via the router vs
       direct HTTP to the hosting replica; ``mesh_router_hop_ms`` is the
       p50 delta (what the routing tier itself adds per request).
    4. **Chaos** (``kill_replica``) — re-run the closed loop while
       SIGKILLing one replica mid-load; callers retry explicit 429/503s.
       ``mesh_kill_lost_requests`` MUST be 0 (every request eventually
       answered correctly), ``mesh_kill_retries`` counts the retried
       hops, and the router must have regrouped (generation bump).
    5. **Trace** — one ``traceparent``-carrying request through the real
       HTTP front end; ``mesh_trace_linked`` is True only if
       ``/debug/requests`` renders router+replica spans as ONE tree.

    Host-side and CPU-capable like the other microbenches.
    ``mesh_host_cpus`` rides the config identity: N processes cannot
    scale past the cores the box has, so scale efficiency is only
    comparable at one CPU count (on this repo's 1-core CI container the
    honest efficiency is ≤ 1/replicas — the artifact records it with
    the context rather than inventing parallelism; see BENCH_NOTES.md).
    """
    import shutil
    import signal as _signal
    import subprocess as _subprocess
    import tempfile as _tempfile
    import threading

    import numpy as np

    from tensorflowonspark_tpu import compat, mesh, online, serving
    from tensorflowonspark_tpu.obs import trace as trace_lib

    rng = np.random.default_rng(0)
    w1 = (rng.standard_normal((feature_dim, hidden_dim))
          .astype(np.float32) * (2.0 / feature_dim) ** 0.5)
    w2 = (rng.standard_normal((hidden_dim, out_dim))
          .astype(np.float32) * (2.0 / hidden_dim) ** 0.5)
    rows_total = clients * reqs_per_client
    feats = rng.standard_normal(
        (rows_total, feature_dim)).astype(np.float32)
    hidden = np.maximum(feats @ w1, 0.0)
    # denser low end than the r11 ladder: mesh load is spread over
    # replicas×tenants, so per-batch coalesce sizes are small (arrival ÷
    # service per tenant) and a [bs/4 ..] ladder pads most batches 4-8×
    bucket_sizes = [max(1, batch_size // 16), max(1, batch_size // 4),
                    batch_size]

    def mlp_fwd(state, batch):
        import jax

        p = state["params"]
        return {"score": jax.nn.relu(
            batch["features"] @ p["w1"]) @ p["w2"]}

    def remaining() -> float:
        return deadline.remaining() if deadline is not None else 1e9

    tmpdir = _tempfile.mkdtemp(prefix="tfos_mesh_")
    router = None
    front = None
    procs: list = []
    logs: list = []
    single = None
    out: dict = {}
    try:
        # one export per tenant (distinct weights → output-verifiable
        # routing); tenant i scales the head so a misroute is a WRONG
        # ANSWER, not a coincidence
        scales = [1.0 + 0.5 * i for i in range(replicas)]
        exports = []
        for i, s in enumerate(scales):
            d = os.path.join(tmpdir, f"export{i}")
            compat.export_saved_model(
                {"params": {"w1": w1, "w2": (w2 * s).astype(np.float32)}},
                d, forward_fn=mlp_fwd,
                example_batch={"features": np.zeros((2, feature_dim),
                                                    np.float32)})
            exports.append(d)
        expected = [hidden @ (w2 * s) for s in scales]
        tenant_of = [ci % replicas for ci in range(clients)]

        def tenant_kw(i):
            return dict(export_dir=exports[i], batch_size=batch_size,
                        bucket_sizes=list(bucket_sizes),
                        input_mapping={"features": "features"},
                        flush_ms=flush_ms, max_pending_mb=64.0)

        # -- phase 1: the single-process r11 baseline -----------------------
        single = online.OnlineServer()
        for i in range(replicas):
            single.add_tenant(f"t{i}", **tenant_kw(i))
        single.start()

        def run_loop(call, check=True, retryable=False,
                     on_progress=None) -> tuple[float, list, list, int]:
            lats: list[list[float]] = [[] for _ in range(clients)]
            errs: list[str] = []
            retries = [0]

            def client(ci: int) -> None:
                ti = tenant_of[ci]
                base = ci * reqs_per_client
                try:
                    for k in range(reqs_per_client):
                        ri = base + k
                        t0 = time.perf_counter()
                        per_req = time.monotonic() + 120.0
                        while True:
                            got = call(ti, ri)
                            if got is not None:
                                break
                            if not retryable:
                                raise RuntimeError("non-retryable miss")
                            if time.monotonic() > per_req:
                                raise RuntimeError(
                                    f"row {ri} still unanswered after "
                                    "120s of retries")
                            retries[0] += 1
                            time.sleep(0.05)
                        lats[ci].append(time.perf_counter() - t0)
                        if check and not np.allclose(
                                got, expected[ti][ri:ri + 1], atol=1e-4):
                            raise RuntimeError(
                                f"row {ri} (tenant t{ti}): output "
                                "diverges — misroute or corruption")
                        if on_progress is not None:
                            on_progress()
                except Exception as e:
                    errs.append(f"client {ci}: {e!r}")

            threads = [threading.Thread(target=client, args=(ci,),
                                        daemon=True)
                       for ci in range(clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300.0)
            wall = time.perf_counter() - t0
            if any(t.is_alive() for t in threads):
                errs.append("client thread(s) alive after 300s — wedged "
                            "caller")
            return wall, [v for per in lats for v in per], errs, retries[0]

        def via_single(ti, ri):
            return single.submit(
                f"t{ti}", {"features": feats[ri:ri + 1]},
                timeout=60.0)["score"]

        via_single(0, 0)  # warm the full path once, un-timed
        s_wall, s_lats, s_errs, _ = run_loop(via_single)
        if s_errs:
            raise RuntimeError("; ".join(s_errs[:3]))
        if len(s_lats) != rows_total:
            raise RuntimeError(
                f"baseline lost replies: {len(s_lats)}/{rows_total}")
        single_rps = rows_total / s_wall
        single_p99 = float(np.percentile(s_lats, 99))
        single.stop()
        single = None

        # -- phase 2: the mesh ----------------------------------------------
        if remaining() < 120:
            raise RuntimeError("wall budget exhausted before the mesh "
                               "phase")
        router = mesh.MeshRouter(
            expected_replicas=replicas, poll_interval=0.25, fail_after=2,
            regroup_timeout=60.0, replica_capacity_mb=256.0,
            min_replicas=1)
        host, port = router.start()
        env = dict(os.environ)
        env[mesh.MESH_AUTH_ENV] = router.auth_token
        for i in range(replicas):
            log = open(os.path.join(tmpdir, f"replica{i}.log"), "wb")
            logs.append(log)
            procs.append(_subprocess.Popen(
                [sys.executable, "-m", "tensorflowonspark_tpu.mesh",
                 "--registry", f"{host}:{port}", "--replica-id", f"r{i}",
                 "--poll-interval", "0.1"],
                stdout=log, stderr=log, env=env,
                cwd=os.path.dirname(os.path.abspath(__file__))))
        try:
            router.await_replicas(
                timeout=min(180.0, max(60.0, remaining() - 90.0)))
        except Exception:
            tails = []
            for i in range(replicas):
                try:
                    with open(os.path.join(
                            tmpdir, f"replica{i}.log")) as f:
                        tails.append(f"r{i}: {f.read()[-300:]}")
                except OSError:
                    pass
            raise RuntimeError(
                "mesh did not form: " + " | ".join(tails)[:600])
        rid_of = {}
        for i in range(replicas):
            rid_of[i] = router.add_tenant(f"t{i}", wait_applied_s=60.0,
                                          **tenant_kw(i))
        if len(set(rid_of.values())) != replicas:
            raise RuntimeError(
                f"tenants not spread 1:1 over replicas: {rid_of}")

        import json as _json

        bodies = [
            _json.dumps({"tenant": f"t{tenant_of[ri // reqs_per_client]}",
                         "inputs": {"features": feats[ri:ri + 1].tolist()}
                         }).encode()
            for ri in range(rows_total)]

        shed_before = int(router._shed_total.value)

        def via_router(ti, ri, retryable=False):
            status, _ct, body, _extra = router.route_predict(bodies[ri],
                                                             {})
            if status == 200:
                doc = _json.loads(body if isinstance(body, str)
                                  else body.decode())
                return np.asarray(doc["outputs"]["score"])
            if retryable and status in (429, 503):
                return None
            raise RuntimeError(f"router returned {status}: "
                               f"{body[:200]}")

        via_router(0, 0)  # warm, un-timed
        m_wall, m_lats, m_errs, _ = run_loop(via_router)
        if m_errs:
            raise RuntimeError("; ".join(m_errs[:3]))
        if len(m_lats) != rows_total:
            raise RuntimeError(
                f"mesh lost replies: {len(m_lats)}/{rows_total}")
        shed = int(router._shed_total.value) - shed_before
        if shed:
            raise RuntimeError(
                f"{shed} router shed(s) during a closed loop sized "
                "inside the admission bound — refusing to stamp")
        mesh_rps = rows_total / m_wall
        mesh_p50 = float(np.percentile(m_lats, 50))
        mesh_p99 = float(np.percentile(m_lats, 99))
        for name, val in (("mesh", mesh_p99),
                          ("single-process", single_p99)):
            if val * 1000 > slo_ms:
                raise RuntimeError(
                    f"{name} p99 {val * 1000:.1f}ms misses the {slo_ms}ms "
                    "SLO — a rows/sec claimed at an SLO it missed is not "
                    "a measurement")

        # -- phase 3: router-hop latency ------------------------------------
        hop_reps = 200
        r0 = router._replicas[rid_of[0]]
        direct_conn = None

        def via_direct_http(ri):
            import http.client as _hc

            nonlocal direct_conn
            if direct_conn is None:
                direct_conn = _hc.HTTPConnection(r0.host, r0.port,
                                                 timeout=30.0)
            direct_conn.request(
                "POST", "/v1/predict", body=bodies[ri],
                headers={"Content-Type": "application/json"})
            resp = direct_conn.getresponse()
            resp.read()
            if resp.status != 200:
                raise RuntimeError(f"direct hop returned {resp.status}")

        # rows 0..reqs_per_client-1 belong to client 0 → tenant t0 →
        # replica r0, so the routed and direct legs hit the SAME replica.
        # Contiguous per-leg blocks (warmed, medians): an interleaved
        # A/B measured a NEGATIVE hop on this box — the replica-side
        # latency jitter under process contention swamps a sub-ms hop,
        # and alternation samples each leg under the other's cache wake
        reps = min(hop_reps, reqs_per_client)
        routed, direct = [], []
        for _ in range(5):  # warm both connections/paths
            via_direct_http(0)
            via_router(0, 0)
        for ri in range(reps):
            t0 = time.perf_counter()
            via_router(0, ri)
            routed.append(time.perf_counter() - t0)
        for ri in range(reps):
            t0 = time.perf_counter()
            via_direct_http(ri)
            direct.append(time.perf_counter() - t0)
        if direct_conn is not None:
            direct_conn.close()
        hop_ms = (float(np.percentile(routed, 50))
                  - float(np.percentile(direct, 50))) * 1000

        # -- phase 4: SIGKILL chaos -----------------------------------------
        kill_fields: dict = {}
        if kill_replica and remaining() > 90:
            victim_rid = rid_of[0]
            victim_idx = int(victim_rid[1:])
            done = [0]
            killed = [False]
            kill_at = rows_total // 4

            def on_progress():
                done[0] += 1
                if not killed[0] and done[0] >= kill_at:
                    killed[0] = True
                    procs[victim_idx].send_signal(_signal.SIGKILL)

            k_wall, k_lats, k_errs, k_retries = run_loop(
                lambda ti, ri: via_router(ti, ri, retryable=True),
                retryable=True, on_progress=on_progress)
            if k_errs:
                raise RuntimeError(
                    "chaos loop lost/wedged requests: "
                    + "; ".join(k_errs[:3]))
            lost = rows_total - len(k_lats)
            if lost:
                raise RuntimeError(
                    f"chaos loop lost {lost} replies — zero-loss "
                    "contract violated")
            st = router.stats()
            if st["generation"] < 1 or victim_rid not in \
                    st["lost_replicas"]:
                raise RuntimeError(
                    "router never regrouped past the SIGKILLed replica")
            kill_fields = {
                "mesh_kill_lost_requests": 0,
                "mesh_kill_retries": int(k_retries),
                "mesh_kill_loop_seconds": round(k_wall, 2),
                "mesh_kill_generation": st["generation"],
            }
        else:
            kill_fields = {
                "mesh_kill_lost_requests": None,
                "mesh_kill_reason": ("kill phase disabled" if not
                                     kill_replica else
                                     "wall budget exhausted before the "
                                     "kill phase"),
            }

        # -- phase 5: one traceparent-linked tree ---------------------------
        trace_linked = False
        try:
            # a dedicated tiny-SLO tenant: its (healthy) request breaches
            # the replica-side SLO, so the replica RETAINS the tree; the
            # bench process samples at 1 so the router side retains too
            surviving = [i for i in range(replicas)
                         if procs[i].poll() is None]
            router.add_tenant("traced", wait_applied_s=60.0,
                              **dict(tenant_kw(surviving[0]),
                                     slo_ms=0.001, max_pending_mb=1.0))
            front = mesh.MeshHTTPServer(router)
            fhost, fport = front.start()
            ctx = trace_lib.TraceContext.new()
            prev_sample = os.environ.get("TFOS_TRACE_SAMPLE")
            os.environ["TFOS_TRACE_SAMPLE"] = "1"
            try:
                import http.client as _hc

                conn = _hc.HTTPConnection(fhost, fport, timeout=30.0)
                conn.request(
                    "POST", "/v1/predict",
                    body=_json.dumps(
                        {"tenant": "traced",
                         "inputs": {"features": feats[:1].tolist()}}),
                    headers={"Content-Type": "application/json",
                             "traceparent": ctx.traceparent()})
                resp = conn.getresponse()
                resp.read()
                conn.close()
                if resp.status == 200:
                    time.sleep(0.3)  # replica-side commit is post-reply
                    merged = router.merged_request_docs()
                    trees = [e for e in merged["retained"]
                             if e["trace_id"] == ctx.trace_id]
                    if trees:
                        names = {s["name"] for s in trees[0]["spans"]}
                        trace_linked = bool(
                            {"mesh.request", "proxy",
                             "online.request"} <= names
                            and trees[0].get("merged_entries", 1) >= 2)
            finally:
                if prev_sample is None:
                    os.environ.pop("TFOS_TRACE_SAMPLE", None)
                else:
                    os.environ["TFOS_TRACE_SAMPLE"] = prev_sample
        except Exception as e:
            print(f"bench: mesh trace-link check failed: {e!r}",
                  file=sys.stderr)

        out = {
            "mesh_rows_per_sec": round(mesh_rps, 1),
            "mesh_rows_per_sec_single_process": round(single_rps, 1),
            "mesh_speedup_vs_single_process": round(
                mesh_rps / single_rps, 3),
            "mesh_scale_efficiency": round(
                mesh_rps / (replicas * single_rps), 3),
            "mesh_p50_ms": round(mesh_p50 * 1000, 3),
            "mesh_p99_ms": round(mesh_p99 * 1000, 3),
            "mesh_p99_ms_single_process": round(single_p99 * 1000, 3),
            "mesh_router_hop_ms": round(hop_ms, 3),
            "mesh_replicas": replicas,
            "mesh_clients": clients,
            "mesh_rows_total": rows_total,
            "mesh_batch_size": batch_size,
            "mesh_feature_dim": feature_dim,
            "mesh_hidden_dim": hidden_dim,
            "mesh_flush_ms": flush_ms,
            "mesh_slo_ms": slo_ms,
            "mesh_bucket_sizes": list(
                serving.resolve_buckets(batch_size, bucket_sizes)),
            "mesh_host_cpus": os.cpu_count(),
            "mesh_trace_linked": trace_linked,
            **kill_fields,
        }
        return out
    finally:
        if single is not None:
            single.stop()
        if front is not None:
            front.stop()
        if router is not None:
            try:
                router.stop(stop_replicas=True)
            except Exception:
                pass
        for proc in procs:
            try:
                proc.terminate()
            except Exception:
                pass
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except Exception:
                try:
                    proc.kill()
                except Exception:
                    pass
        if router is not None:
            try:
                router.server.stop()
            except Exception:
                pass
        for log in logs:
            try:
                log.close()
            except Exception:
                pass
        shutil.rmtree(tmpdir, ignore_errors=True)


def measure_fleet_obs(replicas: int = 2, clients: int = 6,
                      reqs_per_client: int = 40, feature_dim: int = 64,
                      hidden_dim: int = 128, out_dim: int = 4,
                      batch_size: int = 32, flush_ms: float = 2.0,
                      scrape_interval_s: float = 1.0,
                      pairs: int = 3,
                      deadline: "_Deadline | None" = None) -> dict:
    """Fleet-observability microbench (ISSUE 15): the collector's cost
    and its detection claim, through a REAL multi-process mesh.

    Phases:

    1. **Overhead A/B** — ``pairs`` alternating (collector-off,
       collector-on) closed loops of ``clients`` threads through
       ``MeshRouter.route_predict`` (load spread over ``replicas``
       tenants, one per replica process); ``fleet_overhead_frac`` is the
       median over pairs of ``(p99_on − p99_off) / p99_off`` — what the
       scrape+judge tick costs the ROUTER's tail, the one place the
       fleet plane rides the data path's process.
    2. **Induced hot replica** — every client hammers ONE tenant while
       the collector scrapes on its ``scrape_interval_s`` cadence;
       ``fleet_skew_detect_s`` is load-start → the first
       ``fleet.load_skew`` finding naming the hot replica.  Two scrapes
       must bracket the load (≤ 2 cadences) and the judgment must fire
       within ONE further cadence: detection later than
       ``3 × cadence + 1.0s`` (the 1s is subprocess-CI slack) refuses
       to stamp — a skew detector that cannot beat the re-balancing
       loop it feeds is not a detector.
    3. **Schema validation** — ``GET /fleet/metrics`` must validate
       under BOTH ``validate_prometheus_text`` and
       ``validate_openmetrics_text`` with every replica's series
       present (``fleet_metrics_valid``); a federation that emits
       invalid exposition refuses to stamp.

    Host-side and CPU-capable like the other serving microbenches;
    ``fleet_host_cpus`` rides the config identity (the scrape thread
    competes with routing for cores, so the overhead is only comparable
    at one CPU count).
    """
    import shutil
    import subprocess as _subprocess
    import tempfile as _tempfile
    import threading

    import numpy as np

    from tensorflowonspark_tpu import compat, mesh
    from tensorflowonspark_tpu.obs import httpd as _httpd

    rng = np.random.default_rng(7)
    w1 = (rng.standard_normal((feature_dim, hidden_dim))
          .astype(np.float32) * (2.0 / feature_dim) ** 0.5)
    w2 = (rng.standard_normal((hidden_dim, out_dim))
          .astype(np.float32) * (2.0 / hidden_dim) ** 0.5)
    rows_total = clients * reqs_per_client
    feats = rng.standard_normal(
        (rows_total, feature_dim)).astype(np.float32)

    def mlp_fwd(state, batch):
        import jax

        p = state["params"]
        return {"score": jax.nn.relu(
            batch["features"] @ p["w1"]) @ p["w2"]}

    def remaining() -> float:
        return deadline.remaining() if deadline is not None else 1e9

    tmpdir = _tempfile.mkdtemp(prefix="tfos_fleetobs_")
    router = None
    front = None
    procs: list = []
    logs: list = []
    try:
        exports = []
        for i in range(replicas):
            d = os.path.join(tmpdir, f"export{i}")
            compat.export_saved_model(
                {"params": {"w1": w1,
                            "w2": (w2 * (1.0 + 0.5 * i)
                                   ).astype(np.float32)}},
                d, forward_fn=mlp_fwd,
                example_batch={"features": np.zeros((2, feature_dim),
                                                    np.float32)})
            exports.append(d)

        router = mesh.MeshRouter(
            expected_replicas=replicas, poll_interval=scrape_interval_s,
            fail_after=6, regroup_timeout=60.0,
            replica_capacity_mb=256.0, min_replicas=1,
            fleet_window_s=10.0)
        host, port = router.start()
        env = dict(os.environ)
        env[mesh.MESH_AUTH_ENV] = router.auth_token
        for i in range(replicas):
            log = open(os.path.join(tmpdir, f"replica{i}.log"), "wb")
            logs.append(log)
            procs.append(_subprocess.Popen(
                [sys.executable, "-m", "tensorflowonspark_tpu.mesh",
                 "--registry", f"{host}:{port}", "--replica-id", f"r{i}",
                 "--poll-interval", "0.1"],
                stdout=log, stderr=log, env=env,
                cwd=os.path.dirname(os.path.abspath(__file__))))
        router.await_replicas(
            timeout=min(180.0, max(60.0, remaining() - 90.0)))
        rid_of = {}
        for i in range(replicas):
            rid_of[i] = router.add_tenant(
                f"t{i}", wait_applied_s=60.0, export_dir=exports[i],
                batch_size=batch_size,
                bucket_sizes=[max(1, batch_size // 8), batch_size],
                input_mapping={"features": "features"},
                flush_ms=flush_ms, max_pending_mb=64.0)
        if len(set(rid_of.values())) != replicas:
            raise RuntimeError(
                f"tenants not spread 1:1 over replicas: {rid_of}")

        import json as _json

        bodies = [
            _json.dumps(
                {"tenant": f"t{ri % replicas}",
                 "inputs": {"features": feats[ri:ri + 1].tolist()}}
            ).encode()
            for ri in range(rows_total)]
        hot_body = _json.dumps(
            {"tenant": "t0",
             "inputs": {"features": feats[:1].tolist()}}).encode()

        def via_router(ri) -> None:
            status, _ct, body, _extra = router.route_predict(
                bodies[ri], {})
            if status != 200:
                raise RuntimeError(
                    f"router returned {status}: {body[:200]}")

        def closed_loop() -> list:
            lats: list[float] = []
            errs: list[str] = []
            lock = threading.Lock()

            def client(ci: int) -> None:
                try:
                    mine = []
                    for k in range(reqs_per_client):
                        ri = ci * reqs_per_client + k
                        t0 = time.perf_counter()
                        via_router(ri)
                        mine.append(time.perf_counter() - t0)
                    with lock:
                        lats.extend(mine)
                except Exception as e:
                    with lock:
                        errs.append(f"client {ci}: {e!r}")

            threads = [threading.Thread(target=client, args=(ci,),
                                        daemon=True)
                       for ci in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300.0)
            if errs or any(t.is_alive() for t in threads):
                raise RuntimeError("; ".join(errs[:3]) or "wedged caller")
            if len(lats) != rows_total:
                raise RuntimeError(
                    f"lost replies: {len(lats)}/{rows_total}")
            return lats

        via_router(0)  # warm every layer once, un-timed

        # -- phase 1: collector-off vs collector-on router p99 --------------
        fracs, p99s_on, p99s_off = [], [], []
        for _pair in range(pairs):
            if remaining() < 60:
                raise RuntimeError("wall budget exhausted mid-A/B")
            router.set_fleet_enabled(False)
            time.sleep(2 * scrape_interval_s)  # drain in-flight ticks
            off = closed_loop()
            router.set_fleet_enabled(True)
            time.sleep(2 * scrape_interval_s)  # at least one scrape lands
            on = closed_loop()
            p_off = float(np.percentile(off, 99))
            p_on = float(np.percentile(on, 99))
            p99s_off.append(p_off)
            p99s_on.append(p_on)
            fracs.append((p_on - p_off) / p_off)
        overhead = float(np.median(fracs))

        # -- phase 2: induced hot replica → fleet.load_skew ------------------
        if remaining() < 45:
            raise RuntimeError("wall budget exhausted before the skew "
                               "phase")
        hot_rid = rid_of[0]
        stop = threading.Event()
        hammer_errs: list[str] = []

        def hammer() -> None:
            while not stop.is_set():
                try:
                    status, _ct, body, _extra = router.route_predict(
                        hot_body, {})
                    if status != 200:
                        hammer_errs.append(f"status {status}")
                        return
                except Exception as e:
                    hammer_errs.append(repr(e))
                    return

        threads = [threading.Thread(target=hammer, daemon=True)
                   for _ in range(clients)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        detect_s = None
        finding = None
        budget = 3 * scrape_interval_s + 1.0
        try:
            while time.monotonic() - t0 < budget + 2.0:
                report = router.check_fleet()
                hits = [f for f in report["load_skew"]
                        if f["replica"] == hot_rid]
                if hits:
                    detect_s = time.monotonic() - t0
                    finding = hits[0]
                    break
                time.sleep(0.1)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=30.0)
        if hammer_errs:
            raise RuntimeError("hot-load clients failed: "
                               + "; ".join(hammer_errs[:3]))
        if finding is None:
            raise RuntimeError(
                "induced hot replica never raised a fleet.load_skew "
                "finding")
        if detect_s > budget:
            raise RuntimeError(
                f"fleet.load_skew took {detect_s:.2f}s — later than one "
                f"scrape cadence past the earliest detectable window "
                f"({budget:.2f}s at a {scrape_interval_s}s cadence)")

        # -- phase 3: the federated exposition must validate -----------------
        front = mesh.MeshHTTPServer(router)
        fhost, fport = front.start()
        import http.client as _hc

        def fetch(path, accept=None):
            conn = _hc.HTTPConnection(fhost, fport, timeout=30.0)
            conn.request("GET", path,
                         headers={"Accept": accept} if accept else {})
            resp = conn.getresponse()
            body = resp.read().decode()
            conn.close()
            if resp.status != 200:
                raise RuntimeError(f"{path} returned {resp.status}")
            return body

        text = fetch("/fleet/metrics")
        problems = _httpd.validate_prometheus_text(text)
        om = fetch("/fleet/metrics",
                   accept="application/openmetrics-text")
        problems += _httpd.validate_openmetrics_text(om)
        for i in range(replicas):
            if f'replica="r{i}"' not in text:
                problems.append(f"replica r{i} missing from the "
                                "federated exposition")
        if problems:
            raise RuntimeError(
                f"/fleet/metrics failed schema validation: "
                f"{problems[:3]}")

        return {
            "fleet_overhead_frac": round(overhead, 4),
            "fleet_router_p99_ms": round(
                float(np.median(p99s_on)) * 1000, 3),
            "fleet_router_p99_ms_off": round(
                float(np.median(p99s_off)) * 1000, 3),
            "fleet_skew_detect_s": round(detect_s, 3),
            "fleet_skew_replica": hot_rid,
            "fleet_skew_ratio": finding.get("ratio"),
            "fleet_skew_rows_per_sec": finding.get("rows_per_sec"),
            "fleet_metrics_valid": True,
            "fleet_scrape_interval_s": scrape_interval_s,
            "fleet_window_s": router.fleet_window_s,
            "fleet_ring_depth": router.fleet.ring_depth,
            "fleet_replicas": replicas,
            "fleet_clients": clients,
            "fleet_rows_total": rows_total,
            "fleet_host_cpus": os.cpu_count(),
        }
    finally:
        if front is not None:
            front.stop()
        if router is not None:
            try:
                router.stop(stop_replicas=True)
            except Exception:
                pass
        for proc in procs:
            try:
                proc.terminate()
            except Exception:
                pass
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except Exception:
                try:
                    proc.kill()
                except Exception:
                    pass
        if router is not None:
            try:
                router.server.stop()
            except Exception:
                pass
        for log in logs:
            try:
                log.close()
            except Exception:
                pass
        shutil.rmtree(tmpdir, ignore_errors=True)


def measure_incident(replicas: int = 2, clients: int = 6,
                     reqs_per_client: int = 40, feature_dim: int = 64,
                     batch_size: int = 32, flush_ms: float = 2.0,
                     pairs: int = 3,
                     deadline: "_Deadline | None" = None) -> dict:
    """Incident-plane microbench (ISSUE 16): the journal's cost and the
    black-box forensics claim, through a REAL multi-process mesh.

    Phases:

    1. **Overhead A/B** — ``pairs`` alternating (journal-off,
       journal-on) closed loops of ``clients`` threads through
       ``MeshRouter.route_predict``; ``incident_overhead_frac`` is the
       median over pairs of ``(p99_on − p99_off) / p99_off``.  Journal
       events are control-plane transitions, never per-request rows, so
       the per-request cost is one ``enabled()`` check — the acceptance
       claim is that this sits at the noise floor.  The toggle flips
       ``TFOS_JOURNAL`` in the router process (the replicas journal
       throughout: their data path has no per-request emission either).
    2. **Chaos forensics** — traceparent-armed load against a
       microscopic-SLO tenant until ``slo.burn`` fires (journaled as
       ``slo.fire`` with exemplars, black-box bundles broadcast to the
       replicas), then SIGKILL the tenant's replica and reconstruct the
       incident from the spool with ``tools/incident.py``:
       ``incident_timeline_valid`` stamps True only when the merged
       timeline validates, is causally ordered, spans router AND
       corpse, carries the death event with the corpse's stamped
       last-flush, the generation-fenced regroup, and ≥ 1
       exemplar-linked recovered trace.  ``incident_death_latency_s``
       is SIGKILL → the regroup landing (detection + fence, the
       forensic horizon).

    Host-side and CPU-capable like the other serving microbenches.
    """
    import shutil
    import subprocess as _subprocess
    import tempfile as _tempfile
    import threading

    import numpy as np

    from tensorflowonspark_tpu import compat, mesh
    from tensorflowonspark_tpu.obs import journal as _journal_mod
    from tensorflowonspark_tpu.obs import trace as _trace_mod

    _tools = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools")
    if _tools not in sys.path:
        sys.path.insert(0, _tools)
    import check_trace as _check_trace
    import incident as _incident

    rng = np.random.default_rng(16)
    w = (rng.standard_normal((feature_dim, 4)).astype(np.float32)
         * (2.0 / feature_dim) ** 0.5)
    rows_total = clients * reqs_per_client
    feats = rng.standard_normal(
        (rows_total, feature_dim)).astype(np.float32)

    def lin_fwd(state, batch):
        return {"score": batch["x"] @ state["params"]["w"]}

    def remaining() -> float:
        return deadline.remaining() if deadline is not None else 1e9

    tmpdir = _tempfile.mkdtemp(prefix="tfos_incident_")
    spool = os.path.join(tmpdir, "spool")
    os.makedirs(spool)
    prev_env = {k: os.environ.get(k)
                for k in ("TFOS_JOURNAL", _journal_mod.JOURNAL_DIR_ENV)}
    router = None
    procs: list = []
    logs: list = []
    try:
        os.environ["TFOS_JOURNAL"] = "1"
        _journal_mod.configure(spool_dir=spool, flush_interval_s=0.2)
        export = os.path.join(tmpdir, "export")
        compat.export_saved_model(
            {"params": {"w": w}}, export, forward_fn=lin_fwd,
            example_batch={"x": np.zeros((2, feature_dim), np.float32)})

        poll = 0.3
        router = mesh.MeshRouter(
            expected_replicas=replicas, poll_interval=poll,
            fail_after=3, regroup_timeout=60.0,
            replica_capacity_mb=256.0, min_replicas=1,
            fleet_window_s=5.0)
        host, port = router.start()
        env = dict(os.environ)
        env[mesh.MESH_AUTH_ENV] = router.auth_token
        env["TFOS_JOURNAL"] = "1"
        env[_journal_mod.JOURNAL_DIR_ENV] = spool
        env["JAX_PLATFORMS"] = "cpu"
        for i in range(replicas):
            log = open(os.path.join(tmpdir, f"replica{i}.log"), "wb")
            logs.append(log)
            procs.append(_subprocess.Popen(
                [sys.executable, "-m", "tensorflowonspark_tpu.mesh",
                 "--registry", f"{host}:{port}", "--replica-id", f"i{i}",
                 "--poll-interval", "0.1"],
                stdout=log, stderr=log, env=env,
                cwd=os.path.dirname(os.path.abspath(__file__))))
        router.await_replicas(
            timeout=min(180.0, max(60.0, remaining() - 120.0)))

        import json as _json

        # plain tenant for the A/B (no SLO: the off half must not differ
        # from the on half in anything but the journal toggle)
        router.add_tenant(
            "ab", wait_applied_s=60.0, export_dir=export,
            batch_size=batch_size,
            bucket_sizes=[max(1, batch_size // 8), batch_size],
            input_mapping={"x": "x"}, flush_ms=flush_ms,
            max_pending_mb=64.0)
        bodies = [
            _json.dumps({"tenant": "ab",
                         "inputs": {"x": feats[ri:ri + 1].tolist()}}
                        ).encode()
            for ri in range(rows_total)]

        def via_router(ri) -> None:
            status, _ct, body, _extra = router.route_predict(
                bodies[ri], {})
            if status != 200:
                raise RuntimeError(
                    f"router returned {status}: {body[:200]}")

        def closed_loop() -> list:
            lats: list[float] = []
            errs: list[str] = []
            lock = threading.Lock()

            def client(ci: int) -> None:
                try:
                    mine = []
                    for k in range(reqs_per_client):
                        ri = ci * reqs_per_client + k
                        t0 = time.perf_counter()
                        via_router(ri)
                        mine.append(time.perf_counter() - t0)
                    with lock:
                        lats.extend(mine)
                except Exception as e:
                    with lock:
                        errs.append(f"client {ci}: {e!r}")

            threads = [threading.Thread(target=client, args=(ci,),
                                        daemon=True)
                       for ci in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300.0)
            if errs or any(t.is_alive() for t in threads):
                raise RuntimeError("; ".join(errs[:3]) or "wedged caller")
            if len(lats) != rows_total:
                raise RuntimeError(
                    f"lost replies: {len(lats)}/{rows_total}")
            return lats

        closed_loop()  # warm every layer + client thread, un-timed

        # -- phase 1: journal-off vs journal-on router p99 -------------------
        # alternate which half runs first each pair (residual warm-up /
        # drift bias cancels instead of riding one side), then pool the
        # samples per side: a per-pair p99 over a few hundred samples is
        # 2-3 tail events of scheduler jitter, the pooled p99 is not
        all_on: list[float] = []
        all_off: list[float] = []
        for pair in range(pairs):
            if remaining() < 90:
                raise RuntimeError("wall budget exhausted mid-A/B")
            order = ("0", "1") if pair % 2 == 0 else ("1", "0")
            for toggle in order:
                os.environ["TFOS_JOURNAL"] = toggle
                (all_off if toggle == "0" else all_on).extend(
                    closed_loop())
            os.environ["TFOS_JOURNAL"] = "1"
        p_off = float(np.percentile(all_off, 99))
        p_on = float(np.percentile(all_on, 99))
        overhead = (p_on - p_off) / p_off

        # -- phase 2: SIGKILL under load → reconstructed incident ------------
        if remaining() < 60:
            raise RuntimeError("wall budget exhausted before the chaos "
                               "phase")
        # microscopic slo_ms: every request breaches → traces retained,
        # exemplars on the histogram, burn objective red-hot
        victim = router.add_tenant(
            "slo", wait_applied_s=60.0, export_dir=export,
            input_mapping={"x": "x"}, slo_ms=0.0001, flush_ms=flush_ms,
            max_pending_mb=64.0)
        slo_body = _json.dumps(
            {"tenant": "slo",
             "inputs": {"x": feats[:1].tolist()}}).encode()
        t0 = time.monotonic()
        burned = False
        while time.monotonic() - t0 < 30.0:
            ctx = _trace_mod.TraceContext.new()
            status, _ct, _rb, _extra = router.route_predict(
                slo_body, {"traceparent": ctx.traceparent()})
            if status not in (200, 429, 503):
                raise RuntimeError(f"slo tenant returned {status}")
            if any(f["finding"] == "slo.burn"
                   for f in router.check_fleet()["slo_burn"]):
                burned = True
                break
            time.sleep(0.02)
        if not burned:
            raise RuntimeError("slo.burn never fired under load")
        t0 = time.monotonic()
        while time.monotonic() - t0 < 15.0:
            if any(e["type"] == "slo.fire"
                   for e in _journal_mod.get_journal().tail(200)):
                break
            time.sleep(0.1)
        else:
            raise RuntimeError("slo.burn finding never journaled as "
                               "slo.fire")

        # the slo.burn fire also broadcast mesh:blackbox — wait for the
        # VICTIM's anomaly bundle to land before killing it: the bundle
        # carries its retained breach traces, the exemplars' other half
        vic_node = f"mesh-replica-{victim}"
        t0 = time.monotonic()
        while time.monotonic() - t0 < 20.0:
            if _journal_mod.blackbox_files(spool, node=vic_node):
                break
            time.sleep(0.1)
        else:
            raise RuntimeError("victim never dumped its anomaly "
                               "black-box bundle")

        idx = int(victim[1:]) if victim[1:].isdigit() else 0
        kill_t0 = time.monotonic()
        os.kill(procs[idx].pid, __import__("signal").SIGKILL)
        death_latency = None
        while time.monotonic() - kill_t0 < 60.0:
            st = router.stats()
            if st["generation"] >= 1 and st["state"] == "watching":
                death_latency = time.monotonic() - kill_t0
                break
            time.sleep(0.2)
        if death_latency is None:
            raise RuntimeError("regroup never landed after SIGKILL")
        _journal_mod.get_journal().flush()
        _journal_mod.blackbox_dump("bench incident wrap-up",
                                   spool_dir=spool)

        out = _incident.reconstruct(spool)
        s = out["summary"]
        problems = _check_trace.validate_doc(out["timeline"])
        problems += [] if s["ordered"] else ["events out of causal order"]
        if "driver" not in s["nodes"]:
            problems.append("router missing from the timeline")
        if f"mesh-replica-{victim}" not in s["nodes"]:
            problems.append("corpse missing from the timeline")
        deaths = [d for d in s["deaths"] if d["replica"] == victim]
        if not deaths or deaths[0]["gen"] < 1:
            problems.append("no generation-fenced death event")
        elif not deaths[0]["corpse"] \
                or not deaths[0]["corpse"].get("events_flushed"):
            problems.append("death event missing the corpse's stamped "
                            "last-flush")
        if not any(victim in (r["lost"] or []) for r in s["regroups"]):
            problems.append("no regroup naming the lost replica")
        if not s["linked"]:
            problems.append("no journaled exemplar resolved to a "
                            "recovered trace")
        if problems:
            raise RuntimeError(
                f"incident reconstruction failed: {problems[:3]}")

        return {
            "incident_overhead_frac": round(overhead, 4),
            "incident_router_p99_ms": round(p_on * 1000, 3),
            "incident_router_p99_ms_off": round(p_off * 1000, 3),
            "incident_timeline_valid": True,
            "incident_death_latency_s": round(death_latency, 3),
            "incident_journal_events": s["events"],
            "incident_bundles": len(s["bundles"]),
            "incident_linked_traces": len(s["linked"]),
            "incident_replicas": replicas,
            "incident_clients": clients,
            "incident_rows_total": rows_total,
            "incident_host_cpus": os.cpu_count(),
        }
    finally:
        if router is not None:
            try:
                router.stop(stop_replicas=True)
            except Exception:
                pass
        for proc in procs:
            try:
                proc.terminate()
            except Exception:
                pass
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except Exception:
                try:
                    proc.kill()
                except Exception:
                    pass
        if router is not None:
            try:
                router.server.stop()
            except Exception:
                pass
        for log in logs:
            try:
                log.close()
            except Exception:
                pass
        for k, v in prev_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        try:
            # un-point the spool (cfg "" → None) so later rounds don't
            # write into the removed tmpdir
            _journal_mod.configure(spool_dir="")
        except Exception:
            pass
        shutil.rmtree(tmpdir, ignore_errors=True)


def measure_costs(tenants: int = 3, clients: int = 6,
                  reqs_per_client: int = 25, feature_dim: int = 8,
                  batch_size: int = 8, flush_ms: float = 2.0,
                  pairs: int = 3, cadence_s: float = 1.0,
                  decode_prompts: int = 4, decode_new_tokens: int = 8,
                  train_steps: int = 10,
                  deadline: "_Deadline | None" = None) -> dict:
    """Cost-accounting microbench (ISSUE 18): the ledger's conservation
    identity, its cost, its detection claim, and the goodput breakdown —
    all through REAL engines, in-process.

    Phases:

    1. **Conservation** — ``clients`` threads drive a mixed-tenant
       closed loop through a real :class:`online.OnlineServer`
       (``tenants`` tenants sharing one export, 1-3 row requests so
       coalesced batches genuinely mix tenants and pad), then a real
       :class:`decode.DecodeEngine` decodes interleaved-tenant prompts.
       ``costs_conservation_ratio`` is
       ``(Σ per-tenant device-seconds + Σ pad-seconds) / Σ engine
       seconds`` over the run's ledger deltas — the apportionment
       identity; a drift past 1% refuses to stamp.  The online plane's
       engine seconds are ALSO cross-checked against the flight
       recorder's independently-accumulated ``compute`` total
       (``costs_flight_ratio``) — the two sum the same per-batch walls
       through different code, so a forward path that skipped its
       charge shows up here.
    2. **Overhead A/B** — ``pairs`` alternating (ledger-off, ledger-on)
       closed loops; ``costs_overhead_frac`` is the median over pairs of
       ``(p99_on − p99_off) / p99_off`` — what per-batch apportionment
       costs the caller's tail.
    3. **Induced dominant tenant** — ``clients − 1`` threads flood one
       tenant while one thread trickles a victim tenant whose 1 ms
       latency objective burns under the induced queueing; a local
       :class:`obs.fleet.FleetCollector` observes real registry
       snapshots at ``cadence_s``; ``costs_skew_detect_s`` is
       flood-start → the first ``fleet.cost_skew`` finding naming the
       dominant tenant.  Detection later than ``3 × cadence + 1.0s``
       refuses to stamp (the fleet microbench's budget discipline).
    4. **Goodput** — a short CPU ``mnist_mlp`` training run with
       periodic checkpoints; ``costs_goodput_breakdown`` is
       :meth:`GoodputLedger.breakdown` over the measured wall, and its
       ``stage_sum_frac`` must reconcile within the flight tolerance.

    Host-side and CPU-capable; ``costs_host_cpus`` rides the config
    identity like the other serving microbenches.
    """
    import shutil
    import tempfile as _tempfile
    import threading

    import numpy as np

    from tensorflowonspark_tpu import compat, obs, online
    from tensorflowonspark_tpu.obs import fleet as _fleet
    from tensorflowonspark_tpu.obs import flight as _flight
    from tensorflowonspark_tpu.obs import ledger as ledger_mod

    rng = np.random.default_rng(11)
    # deliberately non-trivial forward (~ms per batch on one CPU core):
    # the skew phase needs induced queueing to push the victim tenant's
    # tail past its latency objective, and the conservation identity is
    # only interesting over real device-seconds
    hidden = 512
    w_in = (rng.standard_normal((feature_dim, hidden)).astype(np.float32)
            * (2.0 / feature_dim) ** 0.5)
    w_mid = (rng.standard_normal((hidden, hidden)).astype(np.float32)
             * (2.0 / hidden) ** 0.5)
    w_out = (rng.standard_normal((hidden, 4)).astype(np.float32)
             * (2.0 / hidden) ** 0.5)
    rows_pool = rng.standard_normal(
        (clients * reqs_per_client, 3, feature_dim)).astype(np.float32)

    def fwd(params, batch):
        import jax.numpy as jnp

        h = batch["features"] @ params["w_in"]
        for _ in range(8):
            h = jnp.tanh(h @ params["w_mid"])
        return {"score": h @ params["w_out"]}

    def remaining() -> float:
        return deadline.remaining() if deadline is not None else 1e9

    tmpdir = _tempfile.mkdtemp(prefix="tfos_costs_")
    srv = None
    tenant_names = [f"t{i}" for i in range(tenants)]
    try:
        export = os.path.join(tmpdir, "export")
        compat.export_saved_model(
            {"params": {"w_in": w_in, "w_mid": w_mid, "w_out": w_out}},
            export)
        srv = online.OnlineServer()
        for name in tenant_names:
            srv.add_tenant(
                name, export_dir=export, predict_fn=fwd,
                batch_size=batch_size,
                bucket_sizes=[2, batch_size], flush_ms=flush_ms,
                input_mapping={"features": "features"})
        srv.start()

        def closed_loop() -> list:
            lats: list[float] = []
            errs: list[str] = []
            lock = threading.Lock()

            def client(ci: int) -> None:
                try:
                    mine = []
                    for k in range(reqs_per_client):
                        ri = ci * reqs_per_client + k
                        nrows = 1 + ri % 3
                        x = rows_pool[ri][:nrows]
                        t0 = time.perf_counter()
                        srv.submit(tenant_names[ri % tenants],
                                   {"features": x}, timeout=60.0)
                        mine.append(time.perf_counter() - t0)
                    with lock:
                        lats.extend(mine)
                except Exception as e:
                    with lock:
                        errs.append(f"client {ci}: {e!r}")

            threads = [threading.Thread(target=client, args=(ci,),
                                        daemon=True)
                       for ci in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300.0)
            if errs or any(t.is_alive() for t in threads):
                raise RuntimeError("; ".join(errs[:3]) or "wedged caller")
            return lats

        srv.submit(tenant_names[0],
                   {"features": rows_pool[0][:1]}, timeout=60.0)

        # -- phase 1: conservation under concurrent mixed-tenant load --------
        ledger_mod.set_enabled(True)
        led = ledger_mod.get_ledger()
        rec = _flight.recorder("online")
        rec.reset()
        base = led.summary()
        closed_loop()
        from tensorflowonspark_tpu import decode as decode_mod
        from tensorflowonspark_tpu.models import tinylm

        eng = decode_mod.DecodeEngine(
            tinylm.Config.tiny(), max_seqs=4, page_size=8, max_len=64,
            max_prompt_len=24)
        eng.start()
        try:
            prng = np.random.RandomState(5)
            streams = [
                eng.submit(prng.randint(
                    0, tinylm.Config.tiny().vocab_size,
                    size=(4 + i,)).astype(np.int32),
                    max_new_tokens=decode_new_tokens,
                    tenant=tenant_names[i % tenants])
                for i in range(decode_prompts)]
            for s in streams:
                s.result()
        finally:
            eng.stop()
        after = led.summary()

        def _delta(section: str) -> dict:
            out = {}
            for key, doc in after[section].items():
                b = (base[section].get(key)
                     if isinstance(doc, dict) else
                     base[section].get(key, 0.0))
                if isinstance(doc, dict):
                    out[key] = {f: doc[f] - (b or {}).get(f, 0)
                                for f in doc}
                else:
                    out[key] = doc - (b or 0.0)
            return out

        dev_by_tenant = {k: v["device_seconds"]
                         for k, v in _delta("tenants").items()}
        pad_s = sum(_delta("pad_seconds").values())
        engine = _delta("engine_seconds")
        engine_s = sum(engine.values())
        if engine_s <= 0:
            raise RuntimeError("engines recorded zero busy seconds — "
                               "the ledger charged nothing")
        conservation = (sum(dev_by_tenant.values()) + pad_s) / engine_s
        if abs(conservation - 1.0) > 0.01:
            raise RuntimeError(
                f"conservation broke: Σ tenant device-seconds + pad = "
                f"{sum(dev_by_tenant.values()) + pad_s:.6f}s vs engine "
                f"{engine_s:.6f}s (ratio {conservation:.4f})")
        flight_compute = rec.totals().get("compute", 0.0)
        online_engine = engine.get("online", 0.0)
        if flight_compute <= 0:
            raise RuntimeError("online flight recorder saw no compute")
        flight_ratio = online_engine / flight_compute
        if abs(flight_ratio - 1.0) > 0.01:
            raise RuntimeError(
                f"online engine seconds ({online_engine:.6f}s) drifted "
                f"from the flight recorder's compute total "
                f"({flight_compute:.6f}s): some forward path skipped "
                "its charge")

        # -- phase 2: ledger-off vs ledger-on caller p99 ----------------------
        fracs, p99s_on, p99s_off = [], [], []
        for _pair in range(pairs):
            if remaining() < 60:
                raise RuntimeError("wall budget exhausted mid-A/B")
            ledger_mod.set_enabled(False)
            off = closed_loop()
            ledger_mod.set_enabled(True)
            on = closed_loop()
            p_off = float(np.percentile(off, 99))
            p_on = float(np.percentile(on, 99))
            p99s_off.append(p_off)
            p99s_on.append(p_on)
            fracs.append((p_on - p_off) / p_off)
        overhead = float(np.median(fracs))

        # -- phase 3: induced dominant tenant → fleet.cost_skew ---------------
        if remaining() < 45:
            raise RuntimeError("wall budget exhausted before the skew "
                               "phase")
        hog, victim = tenant_names[0], tenant_names[1]
        collector = _fleet.FleetCollector()
        objective = _fleet.Objective(
            f"{victim}-latency", signal="latency", tenant=victim,
            threshold_ms=1.0, budget=0.05,
            fast_window_s=max(4.0, 4 * cadence_s), slow_window_s=120.0,
            burn_threshold=1.0, min_events=5)
        reg = obs.get_registry()
        collector.observe("local", reg.snapshot(), ts=time.time())
        stop = threading.Event()
        flood_errs: list[str] = []
        hot_x = rows_pool[0][:1]

        def flood(name: str) -> None:
            while not stop.is_set():
                try:
                    srv.submit(name, {"features": hot_x}, timeout=60.0)
                except Exception as e:
                    flood_errs.append(repr(e))
                    return

        threads = [threading.Thread(target=flood, args=(hog,),
                                    daemon=True)
                   for _ in range(max(2, clients - 1))]
        threads.append(threading.Thread(target=flood, args=(victim,),
                                        daemon=True))
        t0 = time.monotonic()
        for t in threads:
            t.start()
        detect_s = None
        finding = None
        budget = 3 * cadence_s + 1.0
        try:
            while time.monotonic() - t0 < budget + 2.0:
                time.sleep(cadence_s)
                collector.observe("local", reg.snapshot(),
                                  ts=time.time())
                burns = _fleet.evaluate_slo(
                    collector, [objective], fresh_within_s=60.0)
                hits = [f for f in _fleet.check_costs(
                    collector, burns=burns,
                    window_s=max(10.0, 6 * cadence_s),
                    min_seconds=0.01, fresh_within_s=60.0)
                    if f["tenant"] == hog]
                if hits:
                    detect_s = time.monotonic() - t0
                    finding = hits[0]
                    break
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=30.0)
        if flood_errs:
            raise RuntimeError("flood clients failed: "
                               + "; ".join(flood_errs[:3]))
        if finding is None:
            raise RuntimeError(
                "induced dominant tenant never raised a "
                "fleet.cost_skew finding")
        if detect_s > budget:
            raise RuntimeError(
                f"fleet.cost_skew took {detect_s:.2f}s — later than "
                f"one judgment cadence past the earliest detectable "
                f"window ({budget:.2f}s at a {cadence_s}s cadence)")

        # -- phase 4: goodput breakdown over a real training run --------------
        from tensorflowonspark_tpu.models import mnist
        from tensorflowonspark_tpu.trainer import Trainer

        gp = ledger_mod.goodput()
        gp.reset()
        _flight.recorder("feed").reset()
        cfg = mnist.Config.tiny()
        dim = cfg.image_size * cfg.image_size
        trainer = Trainer("mnist_mlp", config=cfg, learning_rate=1e-2)
        trainer.checkpoint(os.path.join(tmpdir, "ckpt"), every_steps=4)
        images = rng.standard_normal(
            (train_steps, 16, dim)).astype(np.float32)
        labels = rng.integers(
            0, cfg.num_classes, size=(train_steps, 16)).astype(np.int32)
        t0 = time.perf_counter()
        for i in range(train_steps):
            trainer.step({"image": images[i], "label": labels[i]})
        trainer.finish_checkpoints()
        goodput_wall = time.perf_counter() - t0
        breakdown = gp.breakdown(goodput_wall)
        frac = breakdown.get("stage_sum_frac")
        tol = 0.15  # same reconciliation discipline as the flight plane
        if frac is None or abs(frac - 1.0) > tol:
            raise RuntimeError(
                f"goodput breakdown does not reconcile: stage sum is "
                f"{frac} of the measured wall (tolerance {tol})")

        return {
            "costs_conservation_ratio": round(conservation, 4),
            "costs_flight_ratio": round(flight_ratio, 4),
            "costs_overhead_frac": round(overhead, 4),
            "costs_p99_ms": round(
                float(np.median(p99s_on)) * 1000, 3),
            "costs_p99_ms_off": round(
                float(np.median(p99s_off)) * 1000, 3),
            "costs_skew_detect_s": round(detect_s, 3),
            "costs_skew_tenant": finding["tenant"],
            "costs_skew_share": finding["share"],
            "costs_goodput_breakdown": {
                k: breakdown[k] for k in
                ("wall_s", "stage_sum_s", "stage_sum_frac", "phases_s",
                 "productive_frac", "steps")},
            "costs_goodput_productive_frac":
                breakdown["productive_frac"],
            "costs_tenants": tenants,
            "costs_clients": clients,
            "costs_rows_total": clients * reqs_per_client,
            "costs_cadence_s": cadence_s,
            "costs_host_cpus": os.cpu_count(),
        }
    finally:
        ledger_mod.set_enabled(True)
        if srv is not None:
            try:
                srv.stop()
            except Exception:
                pass
        shutil.rmtree(tmpdir, ignore_errors=True)


def _stamp_costs(result: dict, deadline: _Deadline) -> None:
    """Stamp the cost-accounting microbench into the headline result.

    In-process and CPU-capable (real online + decode engines, a real
    trainer — no subprocesses).  The schema is total from r20: failure
    or an exhausted wall budget stamps an explicit null +
    ``costs_reason`` (``tools/bench_gate.py --require-costs-from``)."""
    from tensorflowonspark_tpu import obs

    if deadline.remaining() < 150:
        result["costs_conservation_ratio"] = None
        result["costs_reason"] = ("wall budget exhausted before the "
                                  "cost-accounting microbench")
        return
    with obs.span("bench.costs") as sp:
        try:
            result.update(measure_costs(deadline=deadline))
            sp.set(ok=True,
                   conservation=result.get("costs_conservation_ratio"),
                   overhead_frac=result.get("costs_overhead_frac"),
                   skew_detect_s=result.get("costs_skew_detect_s"))
        except Exception as e:
            result["costs_conservation_ratio"] = None
            result["costs_reason"] = (
                f"cost-accounting microbench failed: {e!r}"[:200])
            sp.set(ok=False, error=str(e)[:200])


def _stamp_fleet(result: dict, deadline: _Deadline) -> None:
    """Stamp the fleet-observability microbench into the headline
    result.

    Host-side like the mesh microbench (replica subprocesses on this
    box, CPU capable).  The schema is total from r17: failure or an
    exhausted wall budget stamps an explicit null + ``fleet_reason``
    (``tools/bench_gate.py --require-fleet-from``)."""
    from tensorflowonspark_tpu import obs

    if deadline.remaining() < 150:
        result["fleet_overhead_frac"] = None
        result["fleet_reason"] = ("wall budget exhausted before the "
                                  "fleet-observability microbench")
        return
    with obs.span("bench.fleet_obs") as sp:
        try:
            result.update(measure_fleet_obs(deadline=deadline))
            sp.set(ok=True,
                   overhead_frac=result.get("fleet_overhead_frac"),
                   skew_detect_s=result.get("fleet_skew_detect_s"))
        except Exception as e:
            result["fleet_overhead_frac"] = None
            result["fleet_reason"] = (
                f"fleet-observability microbench failed: {e!r}"[:200])
            sp.set(ok=False, error=str(e)[:200])


def _stamp_incident(result: dict, deadline: _Deadline) -> None:
    """Stamp the incident-plane microbench into the headline result.

    Host-side like the fleet microbench (replica subprocesses on this
    box, CPU capable).  The schema is total from r18: failure or an
    exhausted wall budget stamps an explicit null + ``incident_reason``
    (``tools/bench_gate.py --require-incident-from``)."""
    from tensorflowonspark_tpu import obs

    if deadline.remaining() < 150:
        result["incident_overhead_frac"] = None
        result["incident_reason"] = ("wall budget exhausted before the "
                                     "incident-plane microbench")
        return
    with obs.span("bench.incident") as sp:
        try:
            result.update(measure_incident(deadline=deadline))
            sp.set(ok=True,
                   overhead_frac=result.get("incident_overhead_frac"),
                   death_latency_s=result.get(
                       "incident_death_latency_s"))
        except Exception as e:
            result["incident_overhead_frac"] = None
            result["incident_reason"] = (
                f"incident-plane microbench failed: {e!r}"[:200])
            sp.set(ok=False, error=str(e)[:200])


def _stamp_mesh(result: dict, deadline: _Deadline) -> None:
    """Stamp the serving-mesh microbench into the headline result.

    Host-side like the others (replica subprocesses on this box, CPU
    capable).  The schema is total from r13: failure or an exhausted
    wall budget stamps an explicit null + ``mesh_reason``
    (``tools/bench_gate.py --require-mesh-from``)."""
    from tensorflowonspark_tpu import obs

    if deadline.remaining() < 180:
        result["mesh_rows_per_sec"] = None
        result["mesh_reason"] = ("wall budget exhausted before serving-"
                                 "mesh microbench")
        return
    with obs.span("bench.serving_mesh") as sp:
        try:
            result.update(measure_serving_mesh(deadline=deadline))
            sp.set(ok=True,
                   rows_per_sec=result.get("mesh_rows_per_sec"),
                   scale_efficiency=result.get("mesh_scale_efficiency"),
                   hop_ms=result.get("mesh_router_hop_ms"))
        except Exception as e:
            result["mesh_rows_per_sec"] = None
            result["mesh_reason"] = (
                f"serving-mesh microbench failed: {e!r}"[:200])
            sp.set(ok=False, error=str(e)[:200])


def _hist_quantile_rows(hist, q: float):
    """Histogram-bucket quantile of the coalesce-size histogram (rows)."""
    from tensorflowonspark_tpu.obs import anomaly

    h = hist.export()
    if not h["count"]:
        return None
    v = anomaly.hist_quantile(h["buckets"], q)
    return None if v is None else round(v, 1)


def _stamp_online(result: dict, deadline: _Deadline) -> None:
    """Stamp the online-serving microbench into the headline result.

    Host-side like the feed/serving/recovery microbenches, so it runs on
    accelerator-degraded rounds too.  The schema is total from r11:
    failure or an exhausted wall budget stamps an explicit null +
    ``online_reason`` (``tools/bench_gate.py --require-online-from``)."""
    from tensorflowonspark_tpu import obs

    if deadline.remaining() < 90:
        result["online_rows_per_sec"] = None
        result["online_reason"] = ("wall budget exhausted before online "
                                   "serving microbench")
        result["trace_overhead_frac"] = None
        result["trace_overhead_reason"] = result["online_reason"]
        return
    with obs.span("bench.serving_online") as sp:
        try:
            result.update(measure_serving_online(deadline=deadline))
            sp.set(ok=True,
                   rows_per_sec=result.get("online_rows_per_sec"),
                   speedup=result.get("online_speedup"),
                   trace_overhead=result.get("trace_overhead_frac"))
        except Exception as e:
            result["online_rows_per_sec"] = None
            result["online_reason"] = (
                f"online serving microbench failed: {e!r}"[:200])
            result["trace_overhead_frac"] = None
            result["trace_overhead_reason"] = result["online_reason"]
            sp.set(ok=False, error=str(e)[:200])


def _stamp_decode(result: dict, deadline: _Deadline) -> None:
    """Stamp the generative-decode microbench into the headline result.

    Host-side like the other serving microbenches, so it runs on
    accelerator-degraded rounds too.  The schema is total from r16:
    failure or an exhausted wall budget stamps an explicit null +
    ``decode_reason`` (``tools/bench_gate.py --require-decode-from``)."""
    from tensorflowonspark_tpu import obs

    if deadline.remaining() < 90:
        result["decode_tokens_per_sec"] = None
        result["decode_reason"] = ("wall budget exhausted before the "
                                   "generative decode microbench")
        return
    with obs.span("bench.serving_decode") as sp:
        try:
            result.update(measure_serving_decode(deadline=deadline))
            sp.set(ok=result.get("decode_tokens_per_sec") is not None,
                   tokens_per_sec=result.get("decode_tokens_per_sec"),
                   speedup=result.get("decode_speedup"))
        except Exception as e:
            result["decode_tokens_per_sec"] = None
            result["decode_reason"] = (
                f"generative decode microbench failed: {e!r}"[:200])
            sp.set(ok=False, error=str(e)[:200])


def _stamp_decode_prefill(result: dict, deadline: _Deadline) -> None:
    """Stamp the chunked-prefill + prefix-sharing microbench.

    Host-side like the decode microbench.  The schema is total from
    r21: failure or an exhausted wall budget stamps an explicit null +
    ``decode_prefill_reason``
    (``tools/bench_gate.py --require-decode-prefill-from``)."""
    from tensorflowonspark_tpu import obs

    if deadline.remaining() < 90:
        result["decode_prefill_short_ttft_ms_p99"] = None
        result["decode_prefill_short_ttft_speedup"] = None
        result["decode_prefill_reason"] = (
            "wall budget exhausted before the chunked-prefill microbench")
        return
    with obs.span("bench.decode_prefill") as sp:
        try:
            result.update(measure_decode_prefill(deadline=deadline))
            sp.set(ok=result.get(
                       "decode_prefill_short_ttft_speedup") is not None,
                   ttft_speedup=result.get(
                       "decode_prefill_short_ttft_speedup"),
                   page_savings=result.get(
                       "decode_prefill_page_savings_frac"))
        except Exception as e:
            result["decode_prefill_short_ttft_ms_p99"] = None
            result["decode_prefill_short_ttft_speedup"] = None
            result["decode_prefill_reason"] = (
                f"chunked-prefill microbench failed: {e!r}"[:200])
            sp.set(ok=False, error=str(e)[:200])


def _stamp_decode_spec(result: dict, deadline: _Deadline) -> None:
    """Stamp the speculative-decoding microbench.

    Host-side like the decode microbench.  The schema is total from
    r22: failure or an exhausted wall budget stamps an explicit null +
    ``spec_reason`` (``tools/bench_gate.py --require-decode-spec-from``)."""
    from tensorflowonspark_tpu import obs

    if deadline.remaining() < 90:
        result["spec_itl_p99_ratio"] = None
        result["spec_reason"] = (
            "wall budget exhausted before the speculative-decode "
            "microbench")
        return
    with obs.span("bench.decode_spec") as sp:
        try:
            result.update(measure_decode_spec(deadline=deadline))
            sp.set(ok=result.get("spec_itl_p99_ratio") is not None,
                   itl_ratio=result.get("spec_itl_p99_ratio"),
                   tokens_per_step=result.get("spec_tokens_per_step"),
                   acceptance=result.get("spec_acceptance_rate"))
        except Exception as e:
            result["spec_itl_p99_ratio"] = None
            result["spec_reason"] = (
                f"speculative-decode microbench failed: {e!r}"[:200])
            sp.set(ok=False, error=str(e)[:200])


def _recovery_train_fun(args, ctx):
    """Elastic map_fun for the recovery microbench: Trainer + periodic
    async checkpoints + regroup cooperation (the REAL elastic path —
    same wiring as production, minus the test-only continuity probes)."""
    from tensorflowonspark_tpu import util

    util.ensure_jax_platform()
    import numpy as np

    from tensorflowonspark_tpu import TFNode, elastic
    from tensorflowonspark_tpu.metrics import MetricsReporter
    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.trainer import Trainer

    def build():
        t = Trainer("mnist_mlp", config=mnist.Config.tiny(),
                    learning_rate=1e-2)
        t.checkpoint(f"{args['model_dir']}/{ctx.job_name}_"
                     f"{ctx.task_index}", every_steps=args["ckpt_every"])
        t.add_step_callback(MetricsReporter(ctx, interval=1))
        return t

    trainer = build()
    worker = elastic.ElasticWorker(ctx, poll_interval=0.25)
    trainer.attach_elastic(worker)
    feed = worker.attach(ctx.get_data_feed(
        train_mode=True, input_mapping=["image", "label"]))
    need_resume_report = False
    while not feed.should_stop():
        try:
            batch = feed.next_batch(args["batch_size"])
            if batch and batch["image"].shape[0] > 0:
                trainer.step(
                    {"image": np.asarray(batch["image"], np.float32),
                     "label": np.asarray(batch["label"], np.int32)})
                if need_resume_report:
                    worker.report_resumed(
                        step=int(np.asarray(trainer.state.step)))
                    need_resume_report = False
        except (TFNode.FeedInterrupted, elastic.RegroupSignal):
            pass
        if worker.regroup_pending():
            trainer.finish_checkpoints()
            worker.rejoin(timeout=120.0)
            trainer = build()
            trainer.attach_elastic(worker)
            trainer.restore_latest()
            need_resume_report = True
    trainer.finish_checkpoints()


def measure_recovery(num_executors: int = 3, ckpt_every: int = 4,
                     kill_at_step: int = 8, batch_size: int = 32,
                     rows: int = 576, num_epochs: int = 16,
                     feed_timeout: float = 180.0) -> dict:
    """Recovery microbench: seconds from SIGKILL to the first post-restore
    step, through the REAL elastic path (ISSUE 8).

    Drives a ``num_executors``-node local-substrate SPARK train with the
    elastic supervisor attached, SIGKILLs one trainer once it reaches
    ``kill_at_step``, and measures SIGKILL → the LAST survivor's first
    post-restore step (the ``elastic:resumed`` kv stamps).  Host-side and
    CPU-capable, so the number is valid on accelerator-degraded runs; it
    bounds the real operational cost of a preemption: detection (manager
    orphan grace + anomaly poll) + generation barrier + checkpoint
    restore + feed replay to the first step.
    """
    import shutil

    import cloudpickle
    import numpy as np

    import tensorflowonspark_tpu.TFCluster as TFClusterMod
    from tensorflowonspark_tpu import elastic
    from tensorflowonspark_tpu.sparkapi import LocalSparkContext

    # the SAME kill protocol the e2e regroup test drives (the chaos
    # helpers live beside the tests; two hand-rolled copies of the
    # poll-and-SIGKILL loop would drift)
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tests"))
    import chaos

    cloudpickle.register_pickle_by_value(sys.modules[__name__])
    # fast detection: the dead node's manager lingers for the orphan
    # grace before the loss is confirmable — the default 15 s is sized
    # for production feed hiccups, not a microbench
    prev_grace = os.environ.get("TFOS_MANAGER_ORPHAN_GRACE_S")
    os.environ["TFOS_MANAGER_ORPHAN_GRACE_S"] = "3"
    tmpdir = tempfile.mkdtemp(prefix="tfos_recovery_bench_")
    sc = LocalSparkContext(f"local-cluster[{num_executors},1,1024]",
                           "recovery-bench")
    out: dict = {
        "recovery_num_executors": num_executors,
        "recovery_ckpt_every_steps": ckpt_every,
        "recovery_kill_at_step": kill_at_step,
        "recovery_batch_size": batch_size,
    }
    cluster = sup = None
    try:
        args = {"model_dir": tmpdir, "ckpt_every": ckpt_every,
                "batch_size": batch_size}
        cluster = TFClusterMod.run(
            sc, _recovery_train_fun, tf_args=args,
            num_executors=num_executors,
            input_mode=TFClusterMod.InputMode.SPARK)
        sup = elastic.ElasticSupervisor(
            cluster, poll_interval=0.5, max_regroups=1,
            regroup_timeout=120.0, resume_wait_s=90.0).start()
        victim = max(cluster.cluster_info, key=lambda m: m["executor_id"])
        kill = chaos.kill_trainer_at_step(cluster, victim,
                                          at_step=kill_at_step,
                                          timeout=240.0,
                                          poll_interval=0.2)
        rng = np.random.default_rng(0)
        data = [(rng.random(64).astype(np.float32), int(i % 10))
                for i in range(rows)]
        sup.train(sc.parallelize(data, num_executors),
                  num_epochs=num_epochs, feed_timeout=feed_timeout,
                  metrics_interval=1.0, detect_timeout=90.0)
        kill["event"].wait(timeout=10.0)
        if "killed_ts" not in kill:
            raise RuntimeError(
                "victim was never killed (training finished first — "
                f"raise num_epochs or lower kill_at_step): "
                f"{kill.get('error')}")
        if sup.generation < 1:
            raise RuntimeError("no regroup happened after the kill")
        record = sup.regroups[0]
        # wait (bounded) for the async recovery stamps
        deadline = time.monotonic() + 90
        while record["recovery_seconds"] is None \
                and time.monotonic() < deadline:
            time.sleep(0.5)
        stamps = cluster.server.kv_items(
            f"{elastic.RESUMED_KEY}:{sup.generation}:")
        if not stamps:
            raise RuntimeError("no survivor stamped a post-restore step")
        # one host by construction (local substrate), so the workers'
        # stamp clocks and the killer's clock agree — this is the
        # SIGKILL-anchored number; the supervisor's detect-anchored view
        # rides along as attribution
        out["recovery_seconds"] = round(
            max(float(v["ts"]) for v in stamps.values())
            - kill["killed_ts"], 3)
        out["recovery_barrier_seconds"] = record["barrier_seconds"]
        out["recovery_detect_to_resume_seconds"] = record[
            "recovery_seconds"]
        out["recovery_generation"] = sup.generation
        out["recovery_survivors"] = len(stamps)
        return out
    finally:
        # teardown in ALL paths: an error mid-measure must not leak a
        # live 3-executor cluster (threads, managers, shm) into the rest
        # of the bench process — it would contend with and corrupt the
        # remaining measurements
        try:
            if cluster is not None:
                cluster.shutdown(grace_secs=90)
        except Exception:
            pass
        if sup is not None:
            sup.stop()
        if prev_grace is None:
            os.environ.pop("TFOS_MANAGER_ORPHAN_GRACE_S", None)
        else:
            os.environ["TFOS_MANAGER_ORPHAN_GRACE_S"] = prev_grace
        sc.stop()
        shutil.rmtree(tmpdir, ignore_errors=True)


def measure_step_collectives(steps: int = 8, batch_per_device: int = 64,
                             hidden: int = 128, depth: int = 6) -> dict:
    """A/B the bucketed, overlapped gradient-collective step against the
    monolithic GSPMD step on the local device set (ISSUE 12).

    Three compiled variants of the SAME step — monolithic (one implicit
    GSPMD exchange), bucketed (explicit per-bucket ``psum`` via
    ``parallel/collectives.py``), and the bucketed step's no-reduce twin
    (identical graph minus the gradient collectives: the compute-only
    floor) — run on identical initial states:

    1. **output equality** first: the bucketed loss trajectory must match
       the monolithic one within the ``tests/test_parallel.py`` f32
       tolerances (rtol=5e-5, atol=1e-7) BEFORE any throughput is
       stamped; a divergence stamps ``step_output_equality: "fail"`` and
       no numbers (the gate fails such an artifact);
    2. **throughput** both ways (``step_rows_per_sec`` /
       ``step_rows_per_sec_monolithic``), each timed to a data-dependent
       loss fetch;
    3. **overlap efficiency**: ``allreduce_overlap_frac = 1 −
       exposed/ideal`` where *exposed* comm is (bucketed − no-reduce)
       per-step wall and *ideal* is the serial all-reduce cost of the
       gradient bytes at the **delivered** ``ici_bw_gbps`` the roofline
       probe measures through the same shard_map+psum flavor — null +
       ``allreduce_overlap_reason`` when the interconnect is
       unmeasurable.

    On a single device (this CI box) there is no cross-replica exchange
    to bucket: everything stamps null + ``step_reason``, and the gate
    judges only within one config identity (device count, platform,
    model, batch, bucket_mb) — like ``mesh_host_cpus`` in r13.
    """
    from tensorflowonspark_tpu import util

    util.ensure_jax_platform()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu.obs import roofline
    from tensorflowonspark_tpu.parallel import (
        MeshConfig,
        build_mesh,
        collectives,
        create_train_state,
        ideal_serial_allreduce_seconds,
        infer_param_sharding,
        make_bucketed_train_step,
        make_train_step,
        shard_batch,
    )

    n_dev = jax.device_count()
    batch_size = batch_per_device * max(1, n_dev)
    out: dict = {
        "step_rows_per_sec": None,
        "step_rows_per_sec_monolithic": None,
        "allreduce_overlap_frac": None,
        "step_platform": jax.default_backend(),
        "step_devices": n_dev,
        "step_model": f"mlp_h{hidden}x{depth}",
        "step_batch_size": batch_size,
    }
    if n_dev < 2:
        out["step_reason"] = ("single device: no cross-replica gradient "
                              "exchange to bucket or overlap")
        return out

    mesh = build_mesh(MeshConfig(dp=n_dev))
    rng = np.random.RandomState(0)
    params: dict = {}
    for i in range(depth):
        params[f"layer{i}"] = {
            "w": jnp.asarray(rng.randn(hidden, hidden) / np.sqrt(hidden),
                             jnp.float32),
            "b": jnp.zeros((hidden,), jnp.float32)}
    params["head"] = {
        "w": jnp.asarray(rng.randn(hidden, 4) / np.sqrt(hidden),
                         jnp.float32),
        "b": jnp.zeros((4,), jnp.float32)}

    def loss_fn(p, batch):
        h = batch["x"]
        for i in range(depth):
            h = jnp.tanh(h @ p[f"layer{i}"]["w"] + p[f"layer{i}"]["b"])
        pred = h @ p["head"]["w"] + p["head"]["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    batch = {"x": rng.randn(batch_size, hidden).astype(np.float32),
             "y": rng.randn(batch_size, 4).astype(np.float32)}
    optimizer = optax.adamw(1e-3)
    shardings = infer_param_sharding(params, mesh)
    grad_bytes = sum(collectives.leaf_bytes(leaf)
                     for leaf in jax.tree_util.tree_leaves(params))
    if os.environ.get("TFOS_ALLREDUCE_BUCKET_MB"):
        bucket_bytes = collectives.bucket_bytes_default()
    else:
        # at toy scale the production default (4 MiB) would put every
        # gradient in one bucket; size for ~4 so the A/B exercises a
        # real multi-bucket schedule.  The actual value rides the config
        # identity either way.
        bucket_bytes = max(16 * 1024, grad_bytes // 4)
    out["step_bucket_mb"] = round(bucket_bytes / (1024 * 1024), 4)
    out["step_grad_mb"] = round(grad_bytes / (1024 * 1024), 4)

    def fresh_state():
        return create_train_state(
            jax.tree_util.tree_map(jnp.copy, params), optimizer)

    sb = shard_batch(mesh, batch)
    # donate=False throughout: states are reused across variants, and the
    # A/B must compare the collective structure, not donation luck
    variants = {
        "monolithic": make_train_step(
            loss_fn, optimizer, mesh, shardings, fresh_state(), batch,
            donate=False, bucketed=False),
        "bucketed": make_bucketed_train_step(
            loss_fn, optimizer, mesh, shardings, fresh_state(), batch,
            donate=False, bucket_bytes=bucket_bytes),
        "noreduce": make_bucketed_train_step(
            loss_fn, optimizer, mesh, shardings, fresh_state(), batch,
            donate=False, bucket_bytes=bucket_bytes, reduce=False),
    }
    out["step_n_buckets"] = variants["bucketed"].n_buckets

    # outputs checked equal BEFORE stamping any throughput
    trajectories = {}
    for name in ("monolithic", "bucketed"):
        st, losses = fresh_state(), []
        for _ in range(4):
            st, loss = variants[name](st, sb)
            losses.append(float(np.asarray(jax.device_get(loss))))
        trajectories[name] = losses
    try:
        np.testing.assert_allclose(trajectories["bucketed"],
                                   trajectories["monolithic"],
                                   rtol=5e-5, atol=1e-7)
        out["step_output_equality"] = "pass"
    except AssertionError as e:
        out["step_output_equality"] = "fail"
        out["step_output_equality_detail"] = str(e)[-300:]
        out["step_reason"] = ("bucketed step diverged from the monolithic "
                              "step: throughput not stamped")
        return out

    def timed(step_fn) -> float:
        st = fresh_state()
        loss = None
        for _ in range(2):  # warmup: compile + first-touch off the clock
            st, loss = step_fn(st, sb)
        float(np.asarray(jax.device_get(loss)))
        t0 = time.perf_counter()
        for _ in range(steps):
            st, loss = step_fn(st, sb)
        # fetch the bytes: the final loss data-depends on every step
        float(np.asarray(jax.device_get(loss)))
        return time.perf_counter() - t0

    dt = {name: timed(step_fn) for name, step_fn in variants.items()}
    out["step_rows_per_sec"] = round(steps * batch_size / dt["bucketed"], 1)
    out["step_rows_per_sec_monolithic"] = round(
        steps * batch_size / dt["monolithic"], 1)
    out["step_seconds_noreduce"] = round(dt["noreduce"] / steps, 6)
    out["step_steps"] = steps

    ici = roofline.measure_ici_bandwidth()
    ideal = ideal_serial_allreduce_seconds(grad_bytes, n_dev,
                                           ici.get("gbps"))
    exposed = max(0.0, (dt["bucketed"] - dt["noreduce"]) / steps)
    if ideal is None:
        out["allreduce_overlap_reason"] = (
            "delivered ICI bandwidth unmeasurable: "
            f"{ici.get('reason', 'no figure')}")
    else:
        frac = 1.0 - exposed / ideal
        out["allreduce_overlap_frac"] = round(max(-1.0, min(1.0, frac)), 4)
        if frac < -1.0:
            # the clamp keeps the gate's [-1,1] schema, but a saturated
            # -1.0 must not masquerade as a measurement: the raw figure
            # rides beside it so a 5x-ideal and a 20x-ideal exposure
            # (launch-overhead-dominated regimes) stay distinguishable
            out["allreduce_overlap_frac_raw"] = round(frac, 4)
        out["allreduce_exposed_ms_per_step"] = round(exposed * 1e3, 4)
        out["allreduce_ideal_serial_ms_per_step"] = round(ideal * 1e3, 4)
        out["step_ici_bw_gbps"] = round(ici["gbps"], 2)
    # the MEASURED comm-vs-compute verdict: unlike the trainer's modelled
    # `_bg` attribution (an upper bound must not name the bottleneck),
    # this exposed-comm figure is real — bucketed minus the no-reduce
    # twin — so it may legitimately classify the step
    from tensorflowonspark_tpu.obs import flight

    out["step_verdict"] = flight.classify(
        {"compute": dt["noreduce"] / steps, "allreduce": exposed})
    return out


def measure_collectives(steps: int = 8, batch_per_device: int = 64,
                        hidden: int = 128, depth: int = 6) -> dict:
    """The sharded-weight-update collectives comparison (ISSUE 17, r19):
    reduce-scatter + in-region 1/N optimizer update + parameter
    all-gather, vs the PR 12 bucketed all-reduce structure.

    Two claims, accounted separately:

    1. **analytic bytes** (``collectives_bytes_ratio``): the
       ``collective_bytes_per_step`` model's gradient-EXCHANGE ratio
       (scatter path / allreduce path) for this toy model's parameter
       tree.  The model needs no second device, so the ratio is numeric
       on every box — evaluated at ``collectives_model_world`` (the real
       device count, floored at 8 so the 1-device CI box still exercises
       the asymptotic claim) and gated < 1 by ``tools/bench_gate.py
       --require-collectives-from`` within config identity (platform,
       devices, dcn_world, model, grad/bucket sizing, update-shard mode);
    2. **measured equivalence + throughput**: with ≥ 2 local devices the
       sharded-update step's 4-step loss trajectory must match the
       all-reduce step's within the established f32 tolerances BEFORE any
       throughput is stamped (``collectives_equality: "fail"`` stamps no
       numbers — broken, not fast), then ``collectives_rows_per_sec``
       times the sharded step.  On a single device both stamp null +
       ``collectives_reason`` — real wall-clock deferred to hardware,
       per the r12/r14 discipline.
    """
    from tensorflowonspark_tpu import util

    util.ensure_jax_platform()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu.parallel import (
        MeshConfig,
        build_mesh,
        collectives,
        create_train_state,
        infer_param_sharding,
        make_bucketed_train_step,
        shard_batch,
    )

    n_dev = jax.device_count()
    batch_size = batch_per_device * max(1, n_dev)
    update_shard = collectives.sharded_update_enabled()
    out: dict = {
        "collectives_bytes_ratio": None,
        "collectives_equality": None,
        "collectives_rows_per_sec": None,
        "collectives_platform": jax.default_backend(),
        "collectives_devices": n_dev,
        "collectives_model": f"mlp_h{hidden}x{depth}",
        "collectives_batch_size": batch_size,
        "collectives_update_shard": bool(update_shard),
    }

    rng = np.random.RandomState(0)
    params: dict = {}
    for i in range(depth):
        params[f"layer{i}"] = {
            "w": jnp.asarray(rng.randn(hidden, hidden) / np.sqrt(hidden),
                             jnp.float32),
            "b": jnp.zeros((hidden,), jnp.float32)}
    params["head"] = {
        "w": jnp.asarray(rng.randn(hidden, 4) / np.sqrt(hidden),
                         jnp.float32),
        "b": jnp.zeros((4,), jnp.float32)}
    param_leaves = jax.tree_util.tree_leaves(params)
    grad_bytes = sum(collectives.leaf_bytes(leaf) for leaf in param_leaves)
    bucket_bytes = max(16 * 1024, grad_bytes // 4)
    # floor low enough that the hidden×hidden kernels (64 KiB) take the
    # scatter path while the bias vectors ride replicated — the mixed
    # plan the analytic model and the HLO tests exercise
    scatter_min = 1024
    out["collectives_grad_mb"] = round(grad_bytes / (1024 * 1024), 4)
    out["collectives_bucket_mb"] = round(bucket_bytes / (1024 * 1024), 4)

    # analytic bytes: numeric on every box (the model is the claim the
    # gate ratchets; wall-clock is a separate, hardware-gated claim)
    model_world = max(n_dev, 8)
    dcn_world = 1
    if n_dev >= 2:
        mesh = build_mesh(MeshConfig(dp=n_dev))
        _stages, dcn_world, _reason = collectives.scatter_stages(mesh, None)
    comm = collectives.collective_bytes_per_step(
        param_leaves, model_world, scatter_min_bytes=scatter_min,
        dcn_world=dcn_world, update_shard=update_shard)
    out["collectives_model_world"] = model_world
    out["collectives_dcn_world"] = dcn_world
    out["collectives_bytes_ratio"] = round(comm["exchange_ratio"], 4)
    mb = 1024.0 * 1024.0
    out["collectives_allreduce_mb"] = round(
        comm["allreduce"]["exchange"] / mb, 4)
    out["collectives_scatter_mb"] = round(
        comm["scatter"]["exchange"] / mb, 4)
    out["collectives_gather_mb"] = round(comm["scatter"]["gather"] / mb, 4)
    out["collectives_scatter_leaves"] = comm["n_scatter_leaves"]

    if n_dev < 2:
        out["collectives_reason"] = (
            "single device: no cross-replica exchange to reduce-scatter; "
            "bytes ratio is analytic at model_world="
            f"{model_world}, wall-clock deferred to hardware")
        return out

    def loss_fn(p, batch):
        h = batch["x"]
        for i in range(depth):
            h = jnp.tanh(h @ p[f"layer{i}"]["w"] + p[f"layer{i}"]["b"])
        pred = h @ p["head"]["w"] + p["head"]["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    batch = {"x": rng.randn(batch_size, hidden).astype(np.float32),
             "y": rng.randn(batch_size, 4).astype(np.float32)}
    optimizer = optax.adamw(1e-3)
    shardings = infer_param_sharding(params, mesh)

    def fresh_state():
        return create_train_state(
            jax.tree_util.tree_map(jnp.copy, params), optimizer)

    sb = shard_batch(mesh, batch)
    allred = make_bucketed_train_step(
        loss_fn, optimizer, mesh, shardings, fresh_state(), batch,
        donate=False, bucket_bytes=bucket_bytes, update_shard=False)
    sharded = make_bucketed_train_step(
        loss_fn, optimizer, mesh, shardings, fresh_state(), batch,
        donate=False, bucket_bytes=bucket_bytes, update_shard=update_shard,
        scatter_min_bytes=scatter_min)
    out["collectives_n_scatter_buckets"] = sharded.n_scatter_buckets
    out["collectives_n_replicated_buckets"] = sharded.n_replicated_buckets

    # equivalence BEFORE throughput: a fast wrong answer is worthless
    trajectories = {}
    for name, step_fn in (("allreduce", allred), ("sharded", sharded)):
        st, losses = fresh_state(), []
        for _ in range(4):
            st, loss = step_fn(st, sb)
            losses.append(float(np.asarray(jax.device_get(loss))))
        trajectories[name] = losses
    try:
        np.testing.assert_allclose(trajectories["sharded"],
                                   trajectories["allreduce"],
                                   rtol=5e-5, atol=1e-7)
        out["collectives_equality"] = "pass"
    except AssertionError as e:
        out["collectives_equality"] = "fail"
        out["collectives_equality_detail"] = str(e)[-300:]
        out["collectives_reason"] = (
            "sharded-update step diverged from the bucketed all-reduce "
            "step: throughput not stamped")
        return out

    def timed(step_fn) -> float:
        st = fresh_state()
        loss = None
        for _ in range(2):
            st, loss = step_fn(st, sb)
        float(np.asarray(jax.device_get(loss)))
        t0 = time.perf_counter()
        for _ in range(steps):
            st, loss = step_fn(st, sb)
        float(np.asarray(jax.device_get(loss)))
        return time.perf_counter() - t0

    dt_sharded = timed(sharded)
    dt_allred = timed(allred)
    out["collectives_rows_per_sec"] = round(
        steps * batch_size / dt_sharded, 1)
    out["collectives_rows_per_sec_allreduce"] = round(
        steps * batch_size / dt_allred, 1)
    out["collectives_steps"] = steps
    return out


def _coldstart_child(cfg_path: str) -> None:
    """Child half of ``measure_compile_cache``: ONE fleet cold start.

    Timed from handler entry (before any jax / framework import — those
    ARE the cold start) through the REAL tenant load path — ``ckpt`` +
    serialized-forward restore via ``pipeline._RunModel._load``,
    ``OnlineServer.add_tenant(warmup=True)`` warming every ladder bucket
    (``compile_cache.ensure()`` runs inside, so the warm compiles
    read/write the configured cache), server start, one submitted request
    served — and reported as ONE JSON line.  The parent controls the
    cache arm via the config's ``cache_dir`` (null = cache off)."""
    t0 = time.perf_counter()
    with open(cfg_path) as f:
        cfg = json.load(f)
    # before jax is imported: the cache-on arm places the cache through
    # jax's own variable (an A/B wants a cold temp dir; the program itself
    # never uses one), the cache-off arm opts out
    if cfg.get("cache_dir"):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cfg["cache_dir"]
    else:
        os.environ["TFOS_COMPILE_CACHE"] = "0"
    import numpy as np

    from tensorflowonspark_tpu import compile_cache, obs, online

    srv = online.OnlineServer()
    try:
        srv.add_tenant(
            "coldstart", export_dir=cfg["export_dir"],
            batch_size=int(cfg["batch_size"]),
            bucket_sizes=list(cfg["bucket_sizes"]),
            input_mapping={"features": "features"}, warmup=True)
        srv.start()
        reply = srv.submit("coldstart", {
            "features": np.zeros((1, int(cfg["width"])), np.float32)},
            timeout=120.0)
        if not reply:
            raise RuntimeError("empty reply from warmed tenant")
        cold = time.perf_counter() - t0
    finally:
        try:
            srv.stop()
        except Exception:
            pass
    import jax

    st = compile_cache.stats()
    print(json.dumps({
        "coldstart_s": round(cold, 4),
        "disk_hits": st["disk_hits"],
        "disk_writes": st["disk_writes"],
        "compiles": int(obs.counter("serving_compiles_total").value),
        "platform": jax.default_backend(),
    }), flush=True)


def _run_coldstart_child(cfg: dict, tmpdir: str, tag: str,
                         timeout_s: float) -> dict:
    """Spawn one ``--_coldstart`` child; returns its JSON (or _error)."""
    cfg_path = os.path.join(tmpdir, f"coldstart_{tag}.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    env = dict(os.environ)
    # the cold-start children are host-side CPU processes (like the mesh
    # replicas): they must not contend with a parent's accelerator, and
    # the per-process XLA compile they measure is backend-independent
    env["JAX_PLATFORMS"] = "cpu"
    # the config decides the arm, not an ambient directory or opt-out
    for name in ("JAX_COMPILATION_CACHE_DIR", "TFOS_COMPILE_CACHE_DIR",
                 "TFOS_COMPILE_CACHE"):
        env.pop(name, None)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--_coldstart", cfg_path],
            capture_output=True, text=True, timeout=timeout_s, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        return {"_error": f"coldstart child timeout after {timeout_s}s"}
    sys.stderr.write(proc.stderr[-2000:])
    for line in reversed(proc.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    tail = (proc.stderr.strip().splitlines() or ["no output"])[-1]
    return {"_error": f"rc={proc.returncode}: {tail[:300]}"}


def measure_compile_cache(layers: int = 96, width: int = 256,
                          batch_size: int = 128,
                          bucket_sizes: "list | None" = None,
                          child_timeout_s: float = 120.0,
                          deadline: "_Deadline | None" = None) -> dict:
    """Fleet cold-start microbench: second-process time-to-first-served-
    request, A/B'd against the persistent compile cache.

    The scenario is ROADMAP item 4's proof obligation: a mesh replica (or
    re-launched trainer) joining a fleet whose shapes are already
    compiled should load executables from the shared cache dir instead of
    re-paying XLA per process.  Three REAL subprocesses, each running the
    full tenant load path (checkpoint restore + serialized-forward
    deserialize + ``add_tenant(warmup=True)`` over the ladder + one
    served request):

    1. **seed** (cache on, empty dir): populates the cache — the "one
       replica compiles" half; also warms OS page caches so the measured
       arms run under equal ambient state;
    2. **cached** (cache on): the claim — ``coldstart_seconds``, which
       must take one disk hit per ladder bucket or the measurement nulls
       itself (a cached number that never touched disk is not evidence);
    3. **nocache** (cache off): the baseline — ``coldstart_seconds_nocache``
       — run LAST, in the warmest slot, so ambient drift biases against
       the cache's claim, not for it.

    The model is a deep narrow MLP (``layers`` × ``width``) exported
    self-describing: compile-heavy relative to its weight bytes, which is
    the regime the cache targets (checkpoint I/O is identical in both
    arms and dilutes the ratio honestly).  Host-side and CPU-capable;
    gated from r15 LOWER-is-better within the
    platform/geometry/ladder/CPU-count config identity.
    """
    import shutil
    import tempfile as _tempfile

    import numpy as np

    from tensorflowonspark_tpu import compat, shapes

    buckets = list(shapes.resolve_buckets(
        batch_size, bucket_sizes or [batch_size // 8, batch_size // 4,
                                     batch_size // 2, batch_size]))

    def remaining() -> float:
        return deadline.remaining() if deadline is not None else 1e9

    tmpdir = _tempfile.mkdtemp(prefix="tfos_coldstart_")
    out: dict = {
        "coldstart_platform": "cpu",
        "coldstart_layers": int(layers),
        "coldstart_width": int(width),
        "coldstart_batch_size": int(batch_size),
        "coldstart_buckets": buckets,
        "coldstart_host_cpus": os.cpu_count(),
    }

    def null(reason: str) -> dict:
        out["coldstart_seconds"] = None
        out["coldstart_reason"] = reason[:300]
        return out

    try:
        import jax.numpy as jnp

        rng = np.random.default_rng(0)
        params = {"layers": [
            (rng.standard_normal((width, width)).astype(np.float32)
             * (1.0 / width) ** 0.5) for _ in range(layers)]}

        def fwd(state, batch):
            x = batch["features"]
            for w in state["params"]["layers"]:
                x = jnp.tanh(x @ w)
            return {"emb": x}

        export_dir = os.path.join(tmpdir, "export")
        compat.export_saved_model(
            {"params": params}, export_dir, forward_fn=fwd,
            example_batch={"features": np.zeros((2, width), np.float32)})

        cache_dir = os.path.join(tmpdir, "cache")
        os.makedirs(cache_dir, exist_ok=True)
        cfg = {"export_dir": export_dir, "batch_size": batch_size,
               "bucket_sizes": buckets, "width": width,
               "cache_dir": cache_dir}

        def child_timeout() -> "float | None":
            # re-checked before EVERY child: a slow earlier child must
            # null as "budget exhausted", not spawn the next child with a
            # zero/negative subprocess timeout and blame it
            left = remaining()
            return min(child_timeout_s, left) if left >= 30 else None

        t = child_timeout()
        if t is None:
            return null("wall budget exhausted before cold-start children")
        seed = _run_coldstart_child(cfg, tmpdir, "seed", t)
        if "_error" in seed:
            return null(f"seed child failed: {seed['_error']}")
        if not seed.get("disk_writes"):
            return null(
                "seed process wrote no persistent-cache entries (backend "
                "ineligible for executable serialization?) — nothing for "
                "a second process to hit")

        t = child_timeout()
        if t is None:
            return null("wall budget exhausted before the cached arm")
        cached = _run_coldstart_child(cfg, tmpdir, "cached", t)
        if "_error" in cached:
            return null(f"cached child failed: {cached['_error']}")
        if int(cached.get("disk_hits") or 0) < len(buckets):
            return null(
                f"second process took {cached.get('disk_hits')} disk hits "
                f"for a {len(buckets)}-bucket ladder — the cached arm did "
                "not actually serve its warm compiles from disk")

        t = child_timeout()
        if t is None:
            return null("wall budget exhausted before the cache-off arm")
        nocache = _run_coldstart_child(
            dict(cfg, cache_dir=None), tmpdir, "nocache", t)
        if "_error" in nocache:
            return null(f"nocache child failed: {nocache['_error']}")

        out["coldstart_platform"] = cached.get("platform", "cpu")
        out["coldstart_seconds"] = float(cached["coldstart_s"])
        out["coldstart_seconds_nocache"] = float(nocache["coldstart_s"])
        out["coldstart_speedup"] = round(
            float(nocache["coldstart_s"]) / float(cached["coldstart_s"]), 3)
        out["coldstart_disk_hits"] = int(cached["disk_hits"])
        out["coldstart_disk_writes"] = int(seed["disk_writes"])
        out["coldstart_compiles"] = int(cached.get("compiles") or 0)
        return out
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def _stamp_compile_cache(result: dict, deadline: _Deadline) -> None:
    """Stamp the compile-cache cold-start A/B into the headline result.

    Host-side (CPU subprocesses) like the feed/serving/recovery
    microbenches, so it runs on accelerator-degraded rounds too.  The
    schema is total from r15: failure or an exhausted wall budget stamps
    an explicit null + ``coldstart_reason``
    (``tools/bench_gate.py --require-coldstart-from``)."""
    from tensorflowonspark_tpu import obs

    if deadline.remaining() < 120:
        result["coldstart_seconds"] = None
        result["coldstart_reason"] = ("wall budget exhausted before "
                                      "compile-cache microbench")
        return
    with obs.span("bench.compile_cache") as sp:
        try:
            result.update(measure_compile_cache(deadline=deadline))
            sp.set(ok=True, seconds=result.get("coldstart_seconds"),
                   speedup=result.get("coldstart_speedup"))
        except Exception as e:
            result["coldstart_seconds"] = None
            result["coldstart_reason"] = (
                f"compile-cache microbench failed: {e!r}"[:200])
            sp.set(ok=False, error=str(e)[:200])


def _stamp_step_collectives(result: dict, deadline: _Deadline) -> None:
    """Stamp the train-step collectives A/B into the headline result.

    Runs on the local device set (the real step path).  The schema is
    total — failure, an exhausted wall budget, or a single device stamps
    an explicit null + ``step_reason`` (``tools/bench_gate.py`` requires
    the fields from r14)."""
    from tensorflowonspark_tpu import obs

    if deadline.remaining() < 60:
        result["step_rows_per_sec"] = None
        result["step_reason"] = ("wall budget exhausted before "
                                 "step-collectives microbench")
        return
    with obs.span("bench.step_collectives") as sp:
        try:
            result.update(measure_step_collectives())
            sp.set(ok=True,
                   rows_per_sec=result.get("step_rows_per_sec"),
                   overlap=result.get("allreduce_overlap_frac"))
        except Exception as e:
            result["step_rows_per_sec"] = None
            result["step_reason"] = (
                f"step-collectives microbench failed: {e!r}"[:200])
            sp.set(ok=False, error=str(e)[:200])


def _stamp_collectives(result: dict, deadline: _Deadline) -> None:
    """Stamp the sharded-weight-update collectives comparison (r19).

    The analytic bytes ratio is numeric on every box; equality and
    throughput need ≥ 2 local devices and otherwise stamp null +
    ``collectives_reason`` (``tools/bench_gate.py`` requires the fields
    from r19)."""
    from tensorflowonspark_tpu import obs

    if deadline.remaining() < 60:
        result["collectives_bytes_ratio"] = None
        result["collectives_reason"] = ("wall budget exhausted before "
                                        "collectives microbench")
        return
    with obs.span("bench.collectives") as sp:
        try:
            result.update(measure_collectives())
            sp.set(ok=True,
                   bytes_ratio=result.get("collectives_bytes_ratio"),
                   equality=result.get("collectives_equality"))
        except Exception as e:
            result["collectives_bytes_ratio"] = None
            result["collectives_reason"] = (
                f"collectives microbench failed: {e!r}"[:200])
            sp.set(ok=False, error=str(e)[:200])


def _stamp_recovery(result: dict, deadline: _Deadline) -> None:
    """Stamp the recovery microbench into the headline result.

    Host-side (local substrate, CPU-capable) like the feed/serving
    microbenches, so it runs on accelerator-degraded rounds too.  The
    schema is total from r10: failure or an exhausted wall budget stamps
    an explicit null + ``recovery_reason``
    (``tools/bench_gate.py --require-recovery-from``)."""
    from tensorflowonspark_tpu import obs

    if deadline.remaining() < 240:
        result["recovery_seconds"] = None
        result["recovery_reason"] = ("wall budget exhausted before "
                                     "recovery microbench")
        return
    with obs.span("bench.recovery") as sp:
        try:
            result.update(measure_recovery())
            sp.set(ok=True, seconds=result.get("recovery_seconds"))
        except Exception as e:
            result["recovery_seconds"] = None
            result["recovery_reason"] = (
                f"recovery microbench failed: {e!r}"[:200])
            sp.set(ok=False, error=str(e)[:200])


def _stamp_serving(result: dict, deadline: _Deadline) -> None:
    """Stamp the serving microbench into the headline result.

    Host-side like the feed microbench: runs even when the accelerator
    halves degraded.  The schema is total — failure or an exhausted wall
    budget stamps an explicit null + ``serve_reason``
    (``tools/bench_gate.py`` requires the field from r08)."""
    from tensorflowonspark_tpu import obs

    if deadline.remaining() < 60:
        result["serve_rows_per_sec"] = None
        result["serve_reason"] = ("wall budget exhausted before serving "
                                  "microbench")
        return
    with obs.span("bench.serving") as sp:
        try:
            result.update(measure_serving())
            sp.set(ok=True,
                   rows_per_sec=result.get("serve_rows_per_sec"),
                   speedup=result.get("serve_speedup"))
        except Exception as e:
            result["serve_rows_per_sec"] = None
            result["serve_reason"] = (
                f"serving microbench failed: {e!r}"[:200])
            sp.set(ok=False, error=str(e)[:200])


def _stamp_feed_transport(result: dict, deadline: _Deadline) -> None:
    """Stamp the feed-transport microbench into the headline result.

    Runs even when the accelerator half degraded — the data plane is
    host-side, so its number stays performance evidence either way.  The
    schema is total: failure or an exhausted wall budget stamps an explicit
    null + ``feed_transport_reason`` (``tools/bench_gate.py`` requires the
    field from r07)."""
    from tensorflowonspark_tpu import obs

    if deadline.remaining() < 60:
        result["feed_rows_per_sec"] = None
        result["feed_transport_reason"] = ("wall budget exhausted before "
                                           "feed microbench")
        return
    with obs.span("bench.feed_transport") as sp:
        try:
            result.update(measure_feed_transport())
            sp.set(ok=True,
                   rows_per_sec=result.get("feed_rows_per_sec"),
                   speedup=result.get("feed_transport_speedup"))
        except Exception as e:
            result["feed_rows_per_sec"] = None
            result["feed_transport_reason"] = (
                f"feed microbench failed: {e!r}"[:200])
            sp.set(ok=False, error=str(e)[:200])


def probe_device(args) -> dict:
    """Liveness probe (child side): prove a tiny device op completes.

    A wedged chip (the round-4 outage mode) accepts dispatches but never
    finishes even trivial matmuls, so the proof is a ``device_get`` of a
    value that data-depends on the matmul, not a readiness ack
    (BENCH_NOTES.md timing methodology).
    """
    from tensorflowonspark_tpu import util

    util.ensure_jax_platform()
    import jax
    import jax.numpy as jnp

    platform = jax.default_backend()
    x = jnp.ones((128, 128), jnp.bfloat16)
    y = jax.jit(lambda a: (a @ a).sum())(x)
    float(jax.device_get(y))
    return {"platform": platform, "ok": True}


def _probe_accelerator(deadline: "_Deadline", reserve_s: float = 0.0) -> dict:
    """Run the liveness probe in a subprocess under a short timeout.

    The whole attempt is spanned (``bench.probe``) so the trace artifact
    attributes the probe window even when the run degrades — the round-5
    bench burned its probe timeout with no record of *where* the 60 s went.
    """
    from tensorflowonspark_tpu import obs

    timeout_s = deadline.clip(_PROBE_TIMEOUT_S, reserve_s=reserve_s)
    # tests shrink _PROBE_TIMEOUT_S below _MIN_CHILD_S; only refuse to spawn
    # when the budget can't even cover the configured probe window
    if timeout_s < min(_MIN_CHILD_S, _PROBE_TIMEOUT_S):
        obs.event("bench.probe_skipped",
                  reason="wall budget exhausted before probe")
        return {"ok": False, "error": "wall budget exhausted before probe"}
    t0 = time.monotonic()
    with obs.span("bench.probe", timeout_s=round(timeout_s, 1)) as sp:
        result = _run_child(["--_probe"], timeout_s)
        if result is not None and result.get("ok"):
            sp.set(ok=True)
            result["probe_s"] = round(time.monotonic() - t0, 1)
            return result
        err = (result or {}).get("_error", "no JSON from probe child")
        sp.set(ok=False, error=err)
    return {"ok": False, "error": err,
            "probe_s": round(time.monotonic() - t0, 1)}


def _run_child(argv: list[str], timeout_s: float) -> dict | None:
    """Run ``bench.py --_measure`` in a subprocess; return its JSON or None."""
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--_measure", *argv],
            capture_output=True,
            text=True,
            timeout=timeout_s,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except subprocess.TimeoutExpired:
        return {"_error": f"timeout after {round(timeout_s)}s"}
    sys.stderr.write(proc.stderr[-4000:])
    for line in reversed(proc.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    tail = (proc.stderr.strip().splitlines() or ["no output"])[-1]
    return {"_error": f"rc={proc.returncode}: {tail[:400]}"}


def _bench_one(model: str, args, deadline: _Deadline, health: dict,
               fallbacks_owed: int = 1, reserve_extra_s: float = 0.0) -> dict:
    """Measure one model fail-soft: accelerator child → CPU child → stub.

    ``health`` is the run-wide accelerator verdict ({"ok": bool, "why": str});
    a probe failure or a hung primary flips it False so LATER models skip
    straight to the CPU fallback instead of re-burning the primary timeout.
    ``fallbacks_owed`` counts CPU fallbacks still possibly needed in this
    invocation (this model's + later models'); that much wall clock is held
    in reserve when sizing the primary child's timeout.  ``reserve_extra_s``
    is additionally held back from BOTH children — the headline run uses it
    to keep room for the mid-run re-probe, which would otherwise be starved
    by a first-half fallback that legitimately runs long.
    """
    from tensorflowonspark_tpu import obs

    passthrough = [f"--model={model}", f"--warmup={args.warmup}"]
    if args.batch_size is not None:
        passthrough.append(f"--batch-size={args.batch_size}")
    if args.steps is not None:
        passthrough.append(f"--steps={args.steps}")

    primary_error = health.get("why", "accelerator marked unhealthy")
    if health.get("ok", True):
        timeout_s = deadline.clip(_PRIMARY_TIMEOUT_S,
                                  reserve_s=fallbacks_owed
                                  * _FALLBACK_RESERVE_S + reserve_extra_s)
        if timeout_s < _MIN_CHILD_S:
            primary_error = "wall budget exhausted before primary attempt"
        else:
            with obs.span("bench.primary", model=model) as sp:
                result = _run_child(passthrough, timeout_s)
                if result is not None and "_error" not in result:
                    sp.set(ok=True)
                    return result
                primary_error = (result or {}).get("_error",
                                                   "no JSON from child")
                sp.set(ok=False, error=primary_error)
            if "timeout" in primary_error:
                # a hung (not merely failed) primary after a green probe:
                # don't let the next model hang too
                health["ok"] = False
                health["why"] = (f"primary attempt for {model} hung: "
                                 f"{primary_error}")
    else:
        obs.event("bench.primary_skipped", model=model, why=primary_error)
    print(f"bench: {model} primary attempt skipped/failed ({primary_error}); "
          "using forced-CPU backend", file=sys.stderr)
    fb_timeout = deadline.clip(_FALLBACK_TIMEOUT_S,
                               reserve_s=(fallbacks_owed - 1)
                               * _FALLBACK_RESERVE_S + reserve_extra_s)
    with obs.span("bench.fallback", model=model) as sp:
        fallback = (_run_child(passthrough + ["--_force-cpu"], fb_timeout)
                    if fb_timeout >= _MIN_CHILD_S
                    else {"_error": "wall budget exhausted before fallback"})
        sp.set(ok=fallback is not None and "_error" not in fallback)
    if fallback is not None and "_error" not in fallback:
        fallback["degraded"] = f"accelerator unavailable: {primary_error}"
        return fallback

    unit, _ = TARGETS[model]
    return {
        "metric": f"{model}_{unit.replace('/', '_per_').replace('.', '')}",
        "value": 0.0,
        "unit": unit,
        "vs_baseline": 0.0,
        "degraded": f"accelerator unavailable: {primary_error}",
        "error": primary_error,
        "fallback_error": (fallback or {}).get("_error", "no JSON from child"),
    }


def _write_trace_artifact(result: dict) -> None:
    """Write the driver-side Chrome-trace artifact and stamp its path.

    Runs on EVERY driver exit path — including degraded/probe-failure
    runs, where the ``bench.probe`` span shows exactly which phase
    consumed the probe timeout (the attribution the round-5 fully-degraded
    artifact lacked).  Best-effort: the bench JSON line must come out even
    if the trace cannot be written.  Path: ``TFOS_BENCH_TRACE_PATH`` or
    ``BENCH_trace.json`` next to this file; validate with
    ``python tools/check_trace.py <path>``.
    """
    path = os.environ.get("TFOS_BENCH_TRACE_PATH") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_trace.json")
    try:
        from tensorflowonspark_tpu import obs

        tracer = obs.get_tracer()
        obs.chrome.write(path, {tracer.node: tracer.snapshot()})
        result["trace_artifact"] = path
    except Exception as e:  # fail-soft by design (see module docstring)
        print(f"bench: could not write trace artifact ({e!r})",
              file=sys.stderr)


def main() -> None:
    args = _parse_args()
    if args._coldstart:
        # fleet cold-start child: timed from HERE (the imports it is about
        # to pay are the cold start) — dispatched before any obs/framework
        # setup the parent path does
        _coldstart_child(args._coldstart)
        return
    if args._probe or args._measure:
        # accelerator-path children honor the outage-simulation knob by
        # hanging BEFORE touching any backend — exactly what a wedged
        # chip does to real work (forced-CPU children stay healthy,
        # like the real fallback path)
        if _simulate_hang_requested(args._force_cpu):
            print("bench: TFOS_BENCH_SIMULATE_HANG — child sleeping",
                  file=sys.stderr, flush=True)
            time.sleep(3600)
    if args._probe:
        print(json.dumps(probe_device(args)))
        return
    if args._measure:
        if args.feed:
            print(json.dumps(measure_feed(args)))
            return
        if args.model is None:
            args.model = "resnet50"
        print(json.dumps(measure(args)))
        return

    _setup_hang_counter()
    from tensorflowonspark_tpu import obs

    obs.configure(node="bench")
    deadline = _Deadline(_WALL_BUDGET_S)

    if args.feed_transport:
        # host-side data-plane measurement: no accelerator, no probe
        result = {"metric": "feed_rows_per_sec", "unit": "rows/sec"}
        _stamp_feed_transport(result, deadline)
        result["value"] = result.get("feed_rows_per_sec")
        _write_trace_artifact(result)
        print(json.dumps(result))
        return

    if args.serving:
        # host-side serving data-plane measurement: no accelerator, no probe
        result = {"metric": "serve_rows_per_sec", "unit": "rows/sec"}
        _stamp_serving(result, deadline)
        result["value"] = result.get("serve_rows_per_sec")
        _write_trace_artifact(result)
        print(json.dumps(result))
        return

    if args.serving_online:
        # host-side online-tier measurement: no accelerator, no probe
        result = {"metric": "online_rows_per_sec", "unit": "rows/sec"}
        _stamp_online(result, deadline)
        result["value"] = result.get("online_rows_per_sec")
        _write_trace_artifact(result)
        print(json.dumps(result))
        return

    if args.serving_decode:
        # host-side generative-decode measurement: no accelerator, no
        # probe
        result = {"metric": "decode_tokens_per_sec", "unit": "tokens/sec"}
        _stamp_decode(result, deadline)
        result["value"] = result.get("decode_tokens_per_sec")
        _write_trace_artifact(result)
        print(json.dumps(result))
        return

    if args.decode_prefill:
        # host-side chunked-prefill/prefix-sharing measurement: no
        # accelerator, no probe
        result = {"metric": "decode_prefill_short_ttft_ms_p99",
                  "unit": "ms"}
        _stamp_decode_prefill(result, deadline)
        result["value"] = result.get("decode_prefill_short_ttft_ms_p99")
        _write_trace_artifact(result)
        print(json.dumps(result))
        return

    if args.decode_spec:
        # host-side speculative-decoding measurement: no accelerator,
        # no probe
        result = {"metric": "spec_itl_p99_ratio", "unit": "ratio"}
        _stamp_decode_spec(result, deadline)
        result["value"] = result.get("spec_itl_p99_ratio")
        _write_trace_artifact(result)
        print(json.dumps(result))
        return

    if args.serving_mesh:
        # host-side multi-process mesh measurement: no accelerator, no
        # probe
        result = {"metric": "mesh_rows_per_sec", "unit": "rows/sec"}
        _stamp_mesh(result, deadline)
        result["value"] = result.get("mesh_rows_per_sec")
        _write_trace_artifact(result)
        print(json.dumps(result))
        return

    if args.fleet_obs:
        # host-side multi-process fleet-observability measurement: no
        # accelerator, no probe
        result = {"metric": "fleet_overhead_frac", "unit": "fraction"}
        _stamp_fleet(result, deadline)
        result["value"] = result.get("fleet_overhead_frac")
        _write_trace_artifact(result)
        print(json.dumps(result))
        return

    if args.incident:
        # host-side multi-process incident-plane measurement: no
        # accelerator, no probe
        result = {"metric": "incident_overhead_frac", "unit": "fraction"}
        _stamp_incident(result, deadline)
        result["value"] = result.get("incident_overhead_frac")
        _write_trace_artifact(result)
        print(json.dumps(result))
        return

    if args.costs:
        # in-process cost-accounting measurement: no accelerator, no
        # probe
        result = {"metric": "costs_conservation_ratio", "unit": "ratio"}
        _stamp_costs(result, deadline)
        result["value"] = result.get("costs_conservation_ratio")
        _write_trace_artifact(result)
        print(json.dumps(result))
        return

    if args.recovery:
        # host-side elastic-recovery measurement: no accelerator, no probe
        result = {"metric": "recovery_seconds", "unit": "seconds"}
        _stamp_recovery(result, deadline)
        result["value"] = result.get("recovery_seconds")
        _write_trace_artifact(result)
        print(json.dumps(result))
        return

    if args.compile_cache:
        # host-side compile-cache cold-start A/B (CPU subprocesses): no
        # accelerator, no probe
        result = {"metric": "coldstart_seconds", "unit": "seconds"}
        _stamp_compile_cache(result, deadline)
        result["value"] = result.get("coldstart_seconds")
        _write_trace_artifact(result)
        print(json.dumps(result))
        return

    if args.step_collectives:
        # local-device-set step-path A/B: no probe (a single device is a
        # legitimate null + reason outcome, not a degraded run)
        result = {"metric": "step_rows_per_sec", "unit": "rows/sec"}
        _stamp_step_collectives(result, deadline)
        result["value"] = result.get("step_rows_per_sec")
        _write_trace_artifact(result)
        print(json.dumps(result))
        return

    if args.collectives:
        # analytic bytes model + local-device-set A/B: no probe (the
        # bytes ratio is numeric even on one device; wall-clock nulls
        # with a reason there)
        result = {"metric": "collectives_bytes_ratio", "unit": "ratio"}
        _stamp_collectives(result, deadline)
        result["value"] = result.get("collectives_bytes_ratio")
        _write_trace_artifact(result)
        print(json.dumps(result))
        return

    probe = _probe_accelerator(deadline)
    probe_failed_at_start = not probe.get("ok")
    health = {"ok": bool(probe.get("ok")),
              "why": f"liveness probe failed: {probe.get('error', '?')}"}
    if not health["ok"]:
        print(f"bench: {health['why']}; skipping all primary attempts",
              file=sys.stderr)

    if args.feed:
        passthrough = ["--feed"]
        if args.batch_size is not None:
            passthrough.append(f"--batch-size={args.batch_size}")
        result = None
        primary_error = health["why"]
        with obs.span("bench.feed"):
            if health["ok"]:
                timeout_s = deadline.clip(_PRIMARY_TIMEOUT_S,
                                          reserve_s=_FALLBACK_RESERVE_S)
                result = (_run_child(passthrough, timeout_s)
                          if timeout_s >= _MIN_CHILD_S else
                          {"_error": "wall budget exhausted"})
                primary_error = (result or {}).get("_error",
                                                   "no JSON from child")
            if result is None or "_error" in result:
                fb_timeout = deadline.clip(_FALLBACK_TIMEOUT_S)
                result = (_run_child(passthrough + ["--_force-cpu"],
                                     fb_timeout)
                          if fb_timeout >= _MIN_CHILD_S
                          else {"_error":
                                "wall budget exhausted before fallback"})
                if result is not None and "_error" not in result:
                    result["degraded"] = (
                        f"accelerator unavailable: {primary_error}")
                else:
                    result = {  # same structured stub shape as _bench_one
                        "metric": "feed_compute_overlap_efficiency",
                        "value": 0.0, "unit": "fraction", "vs_baseline": 0.0,
                        "degraded": f"accelerator unavailable: "
                                    f"{primary_error}",
                        "error": primary_error,
                        "fallback_error": (result or {}).get(
                            "_error", "no JSON from child"),
                    }
        _ensure_roofline_fields(
            result, "no measurement child completed: roofline unmeasured")
        _write_trace_artifact(result)
        print(json.dumps(result))
        return

    if args.model is not None:
        result = _bench_one(args.model, args, deadline, health)
        _ensure_roofline_fields(
            result, "no measurement child completed: roofline unmeasured")
        _write_trace_artifact(result)
        print(json.dumps(result))
        return

    # Headline run (driver invokes with no args): BOTH halves of
    # BASELINE.json::metric — "ResNet-50 images/sec/chip; Criteo wide&deep
    # steps/sec" — in the ONE json line, wide_deep under "secondary".
    # when a re-probe is owed (initial probe failed), hold its time back
    # from the first half's children so a long CPU fallback can't starve it
    reprobe_reserve = _PROBE_TIMEOUT_S if probe_failed_at_start else 0.0
    result = _bench_one("resnet50", args, deadline, health, fallbacks_owed=2,
                        reserve_extra_s=reprobe_reserve)
    if probe_failed_at_start and not health["ok"]:
        # the observed outage flaps: minutes-long healthy windows between
        # wedges.  The first half's CPU fallback has burned a few minutes —
        # ask again before conceding the second half too.
        reprobe = _probe_accelerator(deadline,
                                     reserve_s=_FALLBACK_RESERVE_S)
        probe["reprobe"] = reprobe
        if reprobe.get("ok"):
            print("bench: accelerator came back on re-probe; wide_deep "
                  "gets a primary attempt", file=sys.stderr)
            health["ok"] = True
            health["why"] = "accelerator healthy on re-probe"
    result["secondary"] = _bench_one("wide_deep", args, deadline, health)
    _stamp_feed_transport(result, deadline)
    _stamp_serving(result, deadline)
    _stamp_online(result, deadline)
    _stamp_decode(result, deadline)
    _stamp_decode_prefill(result, deadline)
    _stamp_decode_spec(result, deadline)
    _stamp_recovery(result, deadline)
    _stamp_mesh(result, deadline)
    _stamp_fleet(result, deadline)
    _stamp_incident(result, deadline)
    _stamp_costs(result, deadline)
    _stamp_step_collectives(result, deadline)
    _stamp_collectives(result, deadline)
    _stamp_compile_cache(result, deadline)
    if not probe.get("ok"):
        result["probe"] = probe
    _ensure_roofline_fields(
        result, "no measurement child completed: roofline unmeasured")
    _write_trace_artifact(result)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
