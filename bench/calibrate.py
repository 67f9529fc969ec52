"""Read the numbers that a cell's correctness check compares, over many
seeds in one process: the program's runs, and the control's
(``run.py --control``), from which ``bench/checks/<cell>.json``'s limits are
set (PERF.md).

  python3 bench/calibrate.py --workload moonlight-ep4.decode \\
      --seeds 11,12,13 --control-seeds 21,22,23 --seconds 1

Each seed is one ``run.run_cell`` with its own weights, prompts and
comparison at the cell's sizes, as a benchmark run makes them; only the
window is short (``--seconds``: one round completes at the least), and the
programs compile once for all seeds.  One JSON line per run on standard
output: the seed, whether it was the control, ``correct`` and ``checks``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    runs = [(int(s), False) for s in args.seeds.split(",") if s] + [
        (int(s), True) for s in args.control_seeds.split(",") if s]
    for seed, control in runs:
        argv = ["--workload", args.workload, "--seed", str(seed),
                "--seconds", str(args.seconds), "--trace", "0"]
        rc, result = run.run_cell(run.parse(
            argv + (["--control"] if control else [])))
        if result is None:
            return rc
        print(json.dumps({"seed": seed, "control": control,
                          "correct": result["correct"],
                          "checks": result["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
