"""Readings that the limits of ``limits/<workload>.json`` are set from.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3

For every seed, the numbers compared between the program and the plain
reference, exactly as a benchmark run compares them (the lower
readings). For every control seed also the same numbers for the
reference put in the program's place in bfloat16 (the control), with
half of each minibatch left out and with each round returning the state
it was given (planted faults): the upper readings. One JSON line per reading. The benchmark's own runs never run
this; it needs a TPU like they do.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

VARIANTS = {"control_bfloat16": {"dtype": "bfloat16"},
            "fault_half_batch": {"half_batch": True},
            "fault_state_unchanged": {"frozen": True}}


def readings(cell, seed: int, controls: bool):
    """Yield (kind, numbers, per-leaf gaps) for one seed."""
    from chipbench import harness

    run = harness.Run(cell, seed)
    run.warm_up()
    run.free_program()
    ref = run.reference()
    yield "program", run.numbers(ref), _leaves(run.prog, ref)
    if controls:
        for kind, variant in VARIANTS.items():
            out = run.reference(**variant)
            yield kind, run.numbers(ref, prog=out), _leaves(out, ref)


def _leaves(prog, ref) -> dict:
    from chipbench import check

    return check.leaf_gaps(prog.norms, ref.norms, ref.first_grad)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)

    from chipbench import harness
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    cell = harness.resolve(harness.load_bench(), args.workload)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        for kind, numbers, leaves in readings(cell, seed,
                                              seed in controls):
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "kind": kind, "numbers": numbers,
                              "leaves": leaves,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
