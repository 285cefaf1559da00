"""Readings that set the limits of ``correct``: the program's, and the
control's.

    python bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed this drives the cell's window on the accelerator exactly
as ``bench/run.py`` does and prints, as one JSON line:

* ``program``: what the program produced against the configuration's
  reference in binary64 (the lower reading: it has to be 0);
* ``control``: that reference computed in float32 put in the program's
  place, against the same binary64 replay (the upper reading: a
  comparison that cannot tell it apart is no check).

The benchmark's own runs do not run the control.  Exits 2 off a TPU.
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


def readings(spec, workload, seed, seconds, t_start, root=ROOT, cfg=None,
             traffic=None) -> dict:
    import numpy as np

    import harness

    sink: dict = {}
    result, _ = harness.run_cell(spec, workload, seed, seconds, False, t_start,
                                 root, cfg, traffic, sink)
    drv = sink["program"]
    t0 = time.perf_counter()
    control = drv.ref.compare(harness.replay(drv, sink["cfg"], np.float32),
                              sink["want"])
    return {"seed": seed, "attempted": result["attempted"],
            "program": {k: c["value"] for k, c in result["checks"].items()},
            "control": {k: c["value"] for k, c in
                        harness.checks(control, drv.ref).items()},
            "control_s": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import harness

    spec = harness.load_spec(ROOT)
    refusal = harness.chip_refusal(harness.entry(spec["workloads"], args.workload)["chips"])
    if refusal:
        print(f"control: {refusal}", file=sys.stderr)
        return 2
    t_start = T_START
    for seed in args.seeds:
        print(json.dumps(readings(spec, args.workload, seed, args.seconds, t_start)),
              flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
