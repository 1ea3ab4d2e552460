"""Record the small v5e trace that ``test_benchmark_program_spans.py`` holds
``program_spans.reduce_xplane`` to (run on a chip, by hand; PR 24):

    python tests/benchmark_checks/fixtures/record_spans_trace.py <out_dir>

Six steps of the tiny ResNet preset under ``Trainer`` on one chip, fed by the
program's own pump (``readers.prefetched``) from a generator that stages each
batch under the program's ``feed.stage`` span, each step wrapped in the
benchmark's ``feed_wait`` / ``step_dispatch`` spans as ``trainer_side.py``
wraps them.  Writes the ``.xplane.pb`` (gzipped), the profiler's own Perfetto
export of the same session and the tracer's ring (the ``trainer.step`` spans
on ``time.time()``).

    python tests/benchmark_checks/fixtures/record_spans_trace.py \\
        --expected <perfetto_trace.json.gz>

prints the figures the test holds the reduction to, worked out from that
second reading of the same events (the export carries each operation's
``op_name`` as ``tf_op``), with interval arithmetic of its own.
"""

import glob
import gzip
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, REPO)
STEPS = 6


def main(out_dir: str) -> None:
    from tensorflowonspark_tpu import obs, readers, util

    util.ensure_jax_platform()
    import jax

    from benchmark import trace_reduce
    from tensorflowonspark_tpu.models import resnet
    from tensorflowonspark_tpu.trainer import Trainer

    config = resnet.Config.tiny()
    trainer = Trainer("resnet50", config)
    host = resnet.example_batch(config, batch_size=8)

    def batches():
        for _ in range(STEPS + 4):
            with obs.span("feed.stage"):
                staged = trainer.shard(host)
                time.sleep(0.002)       # a stretch the device idles under
            yield staged

    feed = readers.prefetched(batches, 1, spans=True)
    for _ in range(2):
        float(jax.block_until_ready(trainer.step(next(feed))))
    trace_dir = os.path.join(out_dir, "trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options,
                             create_perfetto_trace=True)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        for _ in range(STEPS):
            with jax.profiler.TraceAnnotation("feed_wait"):
                batch = next(feed)
            with jax.profiler.TraceAnnotation("step_dispatch"):
                float(jax.block_until_ready(trainer.step(batch)))
    jax.profiler.stop_trace()
    feed.close()
    (xplane,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
    with open(xplane, "rb") as src, gzip.open(os.path.join(
            out_dir, "tiny_spans_v5e.xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    for path in glob.glob(os.path.join(trace_dir, "**",
                                       "perfetto_trace.json.gz"),
                          recursive=True):
        shutil.copy(path, os.path.join(out_dir, "perfetto_trace.json.gz"))
    ring = [ev for ev in obs.get_tracer().snapshot() if ev["ph"] == "X"]
    with open(os.path.join(out_dir, "tiny_spans_v5e.ring.json"), "w") as f:
        json.dump({"device_kind": jax.devices()[0].device_kind,
                   "events": ring}, f)
    shutil.rmtree(trace_dir)


def expected(perfetto_path: str) -> dict:
    with gzip.open(perfetto_path, "rt") as f:
        events = json.load(f)["traceEvents"]
    threads = {(e["pid"], e.get("tid")): e["args"]["name"] for e in events
               if e.get("ph") == "M" and e["name"] == "thread_name"}
    procs = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e["name"] == "process_name"}
    spans = [e for e in events if e.get("ph") == "X"]
    (win,) = [e for e in spans if e["name"] == "traced_steps"]
    lo, hi = win["ts"], win["ts"] + win["dur"]
    ops = [e for e in spans if procs[e["pid"]].startswith("/device:TPU")
           and threads[(e["pid"], e["tid"])] == "XLA Ops"]
    idle = [True] * int(round((hi - lo) * 1e3))     # the window in ns
    phases = {}
    for e in ops:
        a, b = max(e["ts"], lo), min(e["ts"] + e["dur"], hi)
        if b <= a:
            continue
        for i in range(int(round((a - lo) * 1e3)), int(round((b - lo) * 1e3))):
            idle[i] = False
        op = e["args"].get("tf_op")
        phase = ("unnamed" if not op else "backward" if "transpose(jvp(" in op
                 else "forward" if "jvp(" in op else "optimizer")
        phases[phase] = phases.get(phase, 0.0) + (b - a)
    staged = [False] * len(idle)
    stages = [e for e in spans if e["name"] == "feed.stage"
              and e["ts"] + e["dur"] > lo and e["ts"] < hi]
    for e in stages:
        a, b = max(e["ts"], lo), min(e["ts"] + e["dur"], hi)
        for i in range(int(round((a - lo) * 1e3)), int(round((b - lo) * 1e3))):
            staged[i] = True
    steps = sum(1 for e in spans if e["name"] == "step_dispatch"
                and lo <= e["ts"] < hi)
    out = {"window_s": (hi - lo) * 1e-6, "steps": steps,
           "idle_s": sum(idle) * 1e-9, "feed_stage_spans": len(stages),
           "idle_staging_pct": 100.0 * sum(
               1 for i, s in zip(idle, staged) if i and s) / len(idle),
           "step_numbers": sorted(int(e["args"]["step"]) for e in spans
                                  if e["name"] == "trainer.step")}
    for phase, us in phases.items():
        out[f"device_{phase}_ms"] = us * 1e-3 / steps
    return out


if __name__ == "__main__":
    if sys.argv[1] == "--expected":
        print(json.dumps(expected(sys.argv[2]), indent=1))
    else:
        os.makedirs(sys.argv[1], exist_ok=True)
        main(sys.argv[1])
