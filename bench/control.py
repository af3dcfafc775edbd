"""Controls and faults on the chip, at a cell's own size.

  python -m bench.control --workload <cell> --seeds 11 12 13 \\
      --plant none replay32 fold32

Runs the cell as ``bench.run`` does (two chunks a run), in one process,
once per planted fault (``bench.faults``; ``none`` plants nothing) and
seed, and prints one JSON line per run with every number that decides
``correct`` beside its limit.  A planted control or fault has to come out
``correct: false``; the sound runs give the lower readings of the limits.
The benchmark's own runs never plant anything.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from bench import faults, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--plant", nargs="+", default=["replay32"],
                    choices=("none",) + faults.NAMES)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))
    for name in args.plant:
        for seed in args.seeds:
            with faults.plant(None if name == "none" else name):
                try:
                    res = run.run_cell(args.workload, seed, args.seconds,
                                       False, t0=time.perf_counter())
                except run.NoChip as e:
                    print(f"bench.control: {e}", file=sys.stderr)
                    return 3
            print(json.dumps({"workload": args.workload, "plant": name,
                              "seed": seed, "correct": res["correct"],
                              "checks": res["checks"],
                              "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
