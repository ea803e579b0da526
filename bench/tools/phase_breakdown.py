"""Split a kept trace's device idle time by the engine's step phases and
JAX's compiles (``perfkit/phases.py``), and optionally write a pruned copy
for the reduction's tests.

    python3 bench/tools/record_trace.py --workload <cell> --seconds 10 --out T.xplane.pb
    python3 bench/tools/phase_breakdown.py T.xplane.pb [--prune OUT.xplane.pb]

Prints one JSON object: the window, busy and idle seconds, the idle shares
under compiles and under host phases, the idle seconds by innermost label
and by innermost phase (where the compiles came from), and each phase's
span count and seconds in the window. ``--prune`` keeps the device's op and
module lines, the harness's spans, the phases, the compile spans and the
jit calls, each within 20 ms of the window (it needs TensorFlow's xplane
protobuf module)."""

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

MARGIN_NS = 20_000_000


def prune(src: str, dst: str) -> None:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from perfkit import phases, tracered

    space = xplane_pb2.XSpace()
    space.ParseFromString(pathlib.Path(src).read_bytes())

    def name(plane, ev) -> str:
        return plane.event_metadata[ev.metadata_id].name.split("#")[0]

    def t_ns(line, ev) -> tuple[int, int]:
        t0 = line.timestamp_ns + ev.offset_ps // 1000
        return t0, t0 + ev.duration_ps // 1000

    window = None
    for plane in space.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if name(plane, ev) == tracered.WINDOW:
                        window = t_ns(line, ev)
    if window is None:
        raise SystemExit(f"no {tracered.WINDOW!r} span in {src}")
    lo, hi = window[0] - MARGIN_NS, window[1] + MARGIN_NS
    keep_host = {tracered.WINDOW, *tracered.HOST_LABELS, *phases.COMPILE_SPANS}
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        device = bool(tracered.DEVICE_PLANE.match(plane.name))
        if not (device or plane.name.startswith("/host:")):
            continue
        p = out.planes.add()
        p.CopyFrom(plane)
        del p.lines[:]
        for line in plane.lines:
            if device and line.name not in (tracered.OP_LINE, tracered.MODULE_LINE):
                continue
            kept = [ev for ev in line.events
                    if (device or name(plane, ev) in keep_host
                        or phases.is_phase(name(plane, ev))
                        or name(plane, ev).startswith(phases.JIT_CALL))
                    and t_ns(line, ev)[1] >= lo and t_ns(line, ev)[0] <= hi]
            if kept:
                q = p.lines.add()
                q.CopyFrom(line)
                del q.events[:]
                q.events.extend(kept)
    pathlib.Path(dst).write_bytes(out.SerializeToString())


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace")
    ap.add_argument("--prune", metavar="OUT")
    args = ap.parse_args()
    from perfkit import phases

    sp = phases.split_file(args.trace)
    print(json.dumps({
        "window_s": sp.window_s, "idle_s": sp.idle_s,
        "idle_compile_share": sp.compile_share(),
        "idle_host_share": sp.host_share(),
        "idle_share": 100.0 * sp.idle_s / sp.window_s,
        "idle_gaps": [[n, s] for n, s in sp.gaps_by_label()],
        "idle_by_phase": [[n, s] for n, s in sp.gaps_by_phase()],
        "phases": {n: list(v) for n, v in sorted(sp.phase_s.items(),
                                                 key=lambda kv: -kv[1][1])}}))
    if args.prune:
        prune(args.trace, args.prune)
    return 0


if __name__ == "__main__":
    sys.exit(main())
