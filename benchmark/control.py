"""Readings for the limits of ``correct``: the program against its reference,
and the control against the same reference, at the cell's own size.

    python3 benchmark/control.py --workload <name> --seeds 11 12 13 [--control-seeds 3]

One process on the chip, no cluster and no measured window (training's
readings need none): for every seed the program's Trainer takes its first
three steps on the cell's first three batches of seeded rows through
``Trainer.step``, the reference follows them, and — on the first
``--control-seeds`` seeds — the control does too: the reference computed in
the precision below the configuration's (``control_precision`` in its
file).  Prints, for every number compared, the largest a sound run gave and
the smallest the control gave.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

GAPS = ("loss_step1_rel", "loss_step2_rel", "loss_step3_rel",
        "first_grad_norm_gap", "param_change_norm_gap")


def program_numbers(jax, cell, program, reference, batches, seed) -> dict:
    from benchmark import trainer_side

    config = cell["config_values"]
    trainer = program.build(config)
    names = program.load_weights(trainer, config, reference, seed)
    mine = {"losses": []}
    for i, batch in enumerate(batches):
        staged = trainer.shard(program.host_batch(dict(batch)))
        mine["losses"].append(float(jax.block_until_ready(
            trainer.step(staged))))
        if i == 0:
            mine["grad_norms"] = program.first_gradient_norms(
                trainer, config, names)
    mine["change_norms"] = trainer_side.change_norms(
        jax, program, reference, trainer, config, seed, names)
    del trainer
    gc.collect()
    return mine


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = p.parse_args(argv)

    from tensorflowonspark_tpu import util

    util.ensure_jax_platform()
    import jax

    from benchmark import check, spec

    spec_ = spec.load(ROOT)
    cell = spec.cell(spec_, args.workload)
    config, traffic = cell["config_values"], cell["traffic_values"]
    program = spec.module(cell["config_package"], "program")
    reference = spec.module(cell["config_package"], "reference")
    generator = spec.module(cell["package"], "traffic", traffic["generator"])
    batch = traffic["batch_per_chip"] * cell["chips"]
    device = jax.devices()[0]
    print(f"device {device.platform} {device.device_kind} x"
          f"{len(jax.devices())}; control precision "
          f"{config['control_precision']}", flush=True)

    rows = []
    for n, seed in enumerate(args.seeds):
        t0 = time.time()
        batches = [generator.rows(traffic, seed,
                                  range(i * batch, (i + 1) * batch))
                   for i in range(3)]
        mine = program_numbers(jax, cell, program, reference, batches, seed)
        t1 = time.time()
        theirs = reference.follow(config, seed, batches)
        t2 = time.time()
        row = {"seed": seed, "sound": check.numbers(mine, theirs),
               "program_s": t1 - t0, "reference_s": t2 - t1,
               "losses": [mine["losses"], theirs["losses"]]}
        if n < args.control_seeds:
            lowered = reference.follow(config, seed, batches,
                                       lower=config["control_precision"])
            row["control"] = check.numbers(lowered, theirs)
            row["control_s"] = time.time() - t2
        rows.append(row)
        print(json.dumps(row), flush=True)

    summary = {}
    for name in GAPS:
        sound = [r["sound"][name] for r in rows]
        control = [r["control"][name] for r in rows if "control" in r]
        summary[name] = {"sound_max": max(sound), "sound_min": min(sound),
                         "control_min": min(control) if control else None,
                         "control_max": max(control) if control else None}
    print(json.dumps({"workload": args.workload, "summary": summary},
                     indent=1))
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"control_{args.workload}.json"),
              "w", encoding="utf-8") as f:
        json.dump({"rows": rows, "summary": summary,
                   "device": [device.platform, device.device_kind]}, f,
                  indent=1)


if __name__ == "__main__":
    main()
