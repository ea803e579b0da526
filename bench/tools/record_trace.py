"""Record a short traced window of a cell on the chip and keep its
``.xplane.pb`` (the reduction's test data comes from here).

    python3 bench/tools/record_trace.py --workload <cell> --seconds 0.4 --out PATH
"""

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
    ap.add_argument("--seconds", type=float, default=0.4)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    from perfkit import driver

    r = driver.run_cell(args.workload, args.seed, args.seconds, True, root=ROOT,
                        keep_trace=args.out)
    print(json.dumps({k: r[k] for k in ("metrics", "device", "breakdown")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
