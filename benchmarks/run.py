"""Benchmark entry point: one section per paper table/figure + the fleet
and roofline analyses.  Prints ``name,value,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--section fig9|roofline|...]
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--section", default="all",
                    help="all | paper | fleet | kernels | roofline")
    args = ap.parse_args()

    from benchmarks import fleet, kernels_bench, paper_figs, roofline
    from repro.launch.compile_cache import enable_compile_cache

    sections = {
        "paper": paper_figs.run,
        "fleet": fleet.run,
        "kernels": kernels_bench.run,
        "roofline": roofline.run,
    }
    wanted = sections if args.section == "all" else \
        {args.section: sections[args.section]}

    enable_compile_cache()
    print("name,value,derived")
    failed = []
    for name, fn in wanted.items():
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:     # noqa: BLE001 -- report, run the rest
            traceback.print_exc()
            print(f"{name}/ERROR,0,{type(e).__name__}: {e}")
            failed.append(name)
            continue
        for row in rows:
            n, v, d = row
            print(f'{n},{v},"{d}"')
        print(f"# section {name} took {time.time()-t0:.1f}s",
              file=sys.stderr)
    if failed:
        raise SystemExit(f"benchmark sections failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
