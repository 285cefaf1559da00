"""Run one benchmark cell on the accelerator this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell's configuration, traffic mix and
metrics are found by name through ``BENCHMARK.json``.  With ``--trace
0`` the result carries the cell's end-to-end metrics; with ``--trace
1`` the benchmark wraps the calls into each layer in spans, records a
profiler trace of the window, and the result carries the per-layer
metrics, the device's busy time and a breakdown.  The last line of
standard output is the result; the numbers compared with the reference
are the last lines of standard error.

Exits 2 with no result when jax's default backend is not a TPU or fewer
chips are visible than the cell asks for: it never measures the CPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import harness

    spec = harness.load_spec(ROOT)
    refusal = harness.chip_refusal(harness.entry(spec["workloads"], args.workload)["chips"])
    if refusal:
        print(f"bench: {refusal}", file=sys.stderr)
        return 2
    result, lines = harness.run_cell(spec, args.workload, args.seed, args.seconds,
                                     bool(args.trace), T_START, ROOT)
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
