"""Runs one cell of the on-chip benchmark once and prints its result.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cells, their configurations and their metrics are listed in
``BENCHMARK.json`` at the root of the checkout. ``--trace 0`` measures
the end-to-end metrics with no profiler and no telemetry attached;
``--trace 1`` reads the per-layer metrics from a window of its own
under ``jax.profiler`` and the program's telemetry. The last line of
standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, [``breakdown``], ``checks``); the
last lines of standard error give each number compared beside its
limit. Exits non-zero, printing no result, without a TPU or with fewer
chips than the cell needs, and when anything compiles inside the
measured window.
"""
from __future__ import annotations

import os
import time


def _seconds_since_process_start() -> float:
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))


#: perf_counter() reading of the moment the process started
T_START = time.perf_counter() - _seconds_since_process_start()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from chipbench import harness

    cell = harness.resolve(harness.load_bench(), args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: needs a TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} chips, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
