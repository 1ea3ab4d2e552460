"""Op-class profiler for zoo models — the BENCH_NOTES methodology, in-tree.

Rounds 3-4 produced the ResNet op-class table (conv fusions / output
fusions / loop fusions, ms per step) from ad-hoc scripts; VERDICT r4 item 4
asks for the same treatment of BERT.  This tool makes the methodology
repeatable: trace N steps with ``jax.profiler.trace``, parse the xplane
proto (via tensorflow's bundled ``tsl`` protobuf — no TF runtime use), and
print per-op-class time sums over the device plane.

Usage (on the bench chip)::

    python tools/profile_model.py --model bert --steps 10
    python tools/profile_model.py --model resnet50 --steps 10

On a chip-less machine add ``--force-cpu --tiny`` (methodology smoke test —
CPU op mix is NOT the TPU op mix).

Classification: events are grouped by the leading HLO opcode token of the
event name (``convolution``, ``dot``, ``all-reduce``, ``copy``, …);
fusions split by their HLO fusion-kind name prefix (``loop_fusion`` /
``output_fusion`` / ``input_fusion``) — the same classes as the
BENCH_NOTES ResNet table.  ``--top N`` prints the N largest raw events for
manual attribution of big fusions.

The xplane proto module (tensorflow's bundled ``tsl`` protobuf) is loaded
BEFORE any JAX device work: importing tensorflow is heavyweight and must
not race the live TPU client for the chip.
"""

from __future__ import annotations

import argparse
import collections
import glob
import os
import re
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="bert")
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--force-cpu", action="store_true")
    p.add_argument("--logdir", default=None,
                   help="keep the raw trace here (default: temp dir)")
    p.add_argument("--top", type=int, default=12,
                   help="also print the N largest individual events")
    return p.parse_args(argv)


from bench import ACCEL_BATCH as _ACCEL_BATCH  # noqa: E402 one source of truth


def _run_trace(args, logdir: str) -> dict:
    if args.force_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("TFOS_NUM_CHIPS", "0")
    from tensorflowonspark_tpu import util

    util.ensure_jax_platform()
    import jax
    import numpy as np

    from tensorflowonspark_tpu import models as model_zoo
    from tensorflowonspark_tpu.trainer import Trainer

    platform = jax.default_backend()
    on_accel = platform in ("tpu", "gpu")
    lib = model_zoo.get_model(args.model)
    config = lib.Config.tiny() if (args.tiny or not on_accel) else lib.Config()
    batch_size = args.batch_size or (
        _ACCEL_BATCH.get(args.model, 32) if on_accel else 16)

    trainer = Trainer(args.model, config=config)
    batch = trainer.shard(lib.example_batch(config, batch_size=batch_size))
    state, loss = trainer.state, None
    for _ in range(args.warmup):
        state, loss = trainer.train_step(state, batch)
    if loss is not None:  # --warmup 0: nothing to sync yet
        float(np.asarray(jax.device_get(loss)).mean())

    t0 = time.perf_counter()
    with jax.profiler.trace(logdir):
        for _ in range(args.steps):
            state, loss = trainer.train_step(state, batch)
        final = float(np.asarray(jax.device_get(loss)).mean())
    wall = time.perf_counter() - t0
    return {"platform": platform, "batch_size": batch_size,
            "steps": args.steps, "wall_s": wall, "loss": final}


_CLASS_PATTERNS = [
    (re.compile(r"^(convolution|conv)"), "convolution (MXU)"),
    (re.compile(r"^(dot|gemm|matmul)"), "dot (MXU)"),
    (re.compile(r"^(all-reduce|all-gather|reduce-scatter|collective-permute"
                r"|all-to-all)"), "collectives"),
    (re.compile(r"^(reduce|reduce-window)"), "reduce"),
    (re.compile(r"^(scatter|gather|dynamic-slice|dynamic-update-slice)"),
     "scatter/gather"),
    (re.compile(r"^(copy|transpose|bitcast|reshape)"), "copy/layout"),
    (re.compile(r"^loop_fusion"), "loop fusion (elementwise)"),
    (re.compile(r"^output_fusion"), "output fusion (reductions)"),
    (re.compile(r"^input_fusion"), "input fusion"),
    (re.compile(r"^fusion"), "fusion (other)"),
    (re.compile(r"^(while|conditional|call)"), "control flow"),
]


def _classify(name: str) -> str:
    base = name.split("%")[-1].strip().lower()
    for pat, cls in _CLASS_PATTERNS:
        if pat.match(base):
            return cls
    return "other"


def _load_xplane_proto():
    """Import the xplane protobuf module.  Called BEFORE any device work:
    the tensorflow import is heavyweight and must not share its first
    initialization with a live JAX TPU client."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    return xplane_pb2


def _parse_xplane(xplane_pb2, logdir: str, top_n: int):
    """Per-op-class duration sums over the device plane of the trace."""
    paths = sorted(glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    space = xplane_pb2.XSpace()
    with open(paths[-1], "rb") as f:
        space.ParseFromString(f.read())

    device_planes = [p for p in space.planes
                     if "/device:" in p.name or "TPU" in p.name]
    if not device_planes:  # CPU backend: host-instrumented XLA modules
        device_planes = [p for p in space.planes if "Host" in p.name
                         or "CPU" in p.name] or list(space.planes)

    per_class: dict[str, float] = collections.defaultdict(float)
    events: list[tuple[float, str]] = []
    for plane in device_planes:
        meta = {m_id: m.name or m.display_name
                for m_id, m in plane.event_metadata.items()}
        # prefer the "XLA Ops" line (leaf HLO ops, no nesting); otherwise
        # take every line but drop python-frame / harness events, which
        # nest and would double-count
        lines = [l for l in plane.lines if "XLA Ops" in l.name] \
            or list(plane.lines)
        for line in lines:
            for ev in line.events:
                name = meta.get(ev.metadata_id, "?")
                if name.startswith("$") or ".py:" in name:
                    continue
                dur_ms = ev.duration_ps / 1e9
                per_class[_classify(name)] += dur_ms
                events.append((dur_ms, name))
    events.sort(reverse=True)
    return per_class, events[:top_n], [p.name for p in device_planes]


def main(argv=None) -> int:
    args = _parse_args(argv)
    logdir = args.logdir or tempfile.mkdtemp(prefix="tfos_profile_")
    xplane_pb2 = _load_xplane_proto()  # before the TPU client exists
    info = _run_trace(args, logdir)
    print(f"trace: model={args.model} platform={info['platform']} "
          f"batch={info['batch_size']} steps={info['steps']} "
          f"wall={info['wall_s']:.2f}s loss={info['loss']:.4g}")
    per_class, top, planes = _parse_xplane(xplane_pb2, logdir, args.top)
    total = sum(per_class.values()) or 1.0
    per_step = info["steps"] or 1
    print(f"planes: {planes}")
    print(f"{'class':24} {'ms/step':>10} {'share':>7}")
    for cls, ms in sorted(per_class.items(), key=lambda kv: -kv[1]):
        print(f"{cls:24} {ms / per_step:10.3f} {ms / total:7.1%}")
    print(f"\ntop {len(top)} events (total ms over {per_step} steps):")
    for dur, name in top:
        print(f"  {dur:10.3f}  {name[:90]}")
    print(f"\nraw trace kept at: {logdir}" if args.logdir else
          f"\n(temp trace dir: {logdir})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
