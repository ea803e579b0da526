"""Read a cell's comparison on many seeds, with the low-precision control
beside it, in one process on the chip.

    python3 bench/tools/control.py --workload <cell> --seeds 1,2,3 --seconds 20

For each seed it runs the cell as the benchmark does (a short window at the
cell's own load) and prints one JSON line: the program's ``correct`` and the
numbers it compared, and the same for the control (the reference at the
configuration's ``control_precision``, one step below its stated precision)
put in the program's place: its first token at each served position, judged
by the same comparison, which must come out not correct."""

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()
    from perfkit import driver

    for seed in (int(s) for s in args.seeds.split(",")):
        r = driver.run_cell(args.workload, seed, args.seconds, False,
                            root=ROOT, control=True)
        ctl = r["diag"]["control"]
        print(json.dumps({
            "seed": seed, "correct": r["correct"],
            "checks": {k: v["value"] for k, v in r["checks"].items()},
            "control_correct": ctl["correct"],
            "control_checks": {k: v["value"] for k, v in ctl["checks"].items()},
            "sampled_kinds": r["diag"]["sampled_kinds"],
            "peak": r["device"]["memory_peak_bytes"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
