"""Run one benchmark cell once, on the chip the process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell, its configuration, its traffic mix
and its metrics are found by name through ``BENCHMARK.json``. The last line
of standard output is the result as one JSON object; the numbers compared
for ``correct`` are the last lines of standard error. Without a TPU, or with
fewer chips than the cell asks for, it exits 2 and prints no result."""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from perfkit import driver

    try:
        result = driver.run_cell(args.workload, args.seed, args.seconds,
                                 bool(args.trace), root=ROOT, t_start=T_START)
    except driver.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
