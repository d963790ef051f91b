"""Find an open-loop cell's knee: the highest offered rate whose queue
does not grow over the window.

  python3 bench/sweep.py --workload <cell> --seconds <s> --seed <n> \\
      --rates 1.5 2 2.5 3

One run per rate, in one process, as ``run.py`` runs the cell with its
traffic file's rate replaced. Each prints its latencies, requests done
against due, and the mean queue depth over the first and the second
half of the window. The knee found is written into the traffic file by
hand, as a number; the benchmark never searches for a rate.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    rows = []
    for rate in args.rates:
        rec: dict = {}
        try:
            res = run.run_cell(run.ROOT, args.workload, args.seed,
                               args.seconds, False, t0=time.perf_counter(),
                               rate=rate, record_out=rec,
                               log=lambda s: print(s, flush=True))
        except run.NoChip as e:
            print(f"[sweep] {e}", file=sys.stderr)
            return 2
        depth = [d for _, d in rec["queue_depth"]]
        half = max(len(depth) // 2, 1)
        row = {"rate": rate, "correct": res["correct"],
               "due": len(rec["latencies_s"]),
               "done": rec["requests"]["completed"],
               "depth_first": sum(depth[:half]) / half,
               "depth_second": (sum(depth[half:]) / max(len(depth) - half, 1)),
               "depth_last": depth[-1] if depth else 0,
               "metrics": {k: v["value"] for k, v in res["metrics"].items()}}
        rows.append(row)
        print(f"[sweep] {json.dumps(row)}", flush=True)
    print(json.dumps({"workload": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
