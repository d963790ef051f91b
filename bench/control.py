"""The control and the program's readings of a cell, seed by seed.

  python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

Runs the cell once per seed in one process, as ``run.py`` does, with
the control in the program's place in the comparison: the same
reference in fp8, whose first token at each served position is judged
in the float32 reference. Each such run has to read ``correct: false``.
On the same sample it also reads the program's own widest logit gap.
The limit of ``check.max_logit_gap`` lies between the largest program
reading over a dozen seeds or more and the smallest control reading.
The benchmark's own runs never run the control. The last stdout line is
a JSON summary.
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

from bench import check, run, spec  # noqa: E402


def _program(args, seed: int, rec: dict) -> dict:
    """The program's own numbers on the sample a control run compared."""
    cell = spec.load_cell(args.workload, run.ROOT)
    ref = spec.reference_module(run.ROOT, cell.config["reference"])
    return check.compare(ref, seed, cell.config["model"], rec["picks"],
                         rec["done"], cell.config["check"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    rows = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        rec: dict = {}
        try:
            res = run.run_cell(run.ROOT, args.workload, seed, args.seconds,
                               False, t0=t0, control=True,
                               record_out=rec,
                               log=lambda s: print(s, flush=True))
        except run.NoChip as e:
            print(f"[control] {e}", file=sys.stderr)
            return 2
        prog = _program(args, seed, rec)
        row = {"seed": seed, "correct": res["correct"],
               "program": prog["max_logit_gap"]["value"],
               "program_correct": check.passed(prog),
               "control": res["check"]["max_logit_gap"]["value"],
               "requests": res["check"]["compared_requests"]["value"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()}}
        rows.append(row)
        print(f"[control] {json.dumps(row)}", flush=True)
    prog = [r["program"] for r in rows]
    print(json.dumps({"workload": args.workload, "rows": rows,
                      "program_max": max(prog),
                      "control_min": min(r["control"] for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
