"""Reduce a profiler trace to the device's busy time, per-operation and
per-kernel device time, and idle gaps labelled by what the host was doing.

The harness wraps its own calls in ``jax.profiler.TraceAnnotation`` spans
(``HOST_LABELS``); a device idle gap is labelled by the innermost such span
that covers its middle. Device operations are the events of the device
planes' op line; the traced window is the ``WINDOW`` span."""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Iterable, Optional, Sequence

WINDOW = "bench.window"
HOST_LABELS = ("engine.step", "engine.submit", "loadgen", "bench.record",
               "bench.idle")
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"


@dataclasses.dataclass
class Interval:
    name: str
    start: float  # seconds
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # mean over the devices seen
    n_devices: int
    op_s: dict[str, float]  # device seconds per operation name (all devices)
    gaps: list[tuple[str, float]]  # (host label, seconds), one per gap
    ops: list[Interval]  # device-0 operations inside the window

    def kernel_s(self, kernel: str) -> float:
        """Device seconds of every call of the Pallas kernel ``kernel`` (its
        operations are named ``%<kernel>.<n>``)."""
        pat = re.compile(r"(^|:)%" + re.escape(kernel) + r"(\.\d+)?$")
        return sum(s for n, s in self.op_s.items() if pat.search(n))

    def gaps_by_label(self) -> list[tuple[str, float]]:
        agg: dict[str, float] = {}
        for label, s in self.gaps:
            agg[label] = agg.get(label, 0.0) + s
        return sorted(agg.items(), key=lambda kv: -kv[1])

    def top_ops(self, n: int = 10) -> list[tuple[str, float]]:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]


def union_length(ivs: Iterable[tuple[float, float]], lo: float, hi: float) -> float:
    """Length of the union of intervals, clipped to [lo, hi]."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in ivs):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(ivs: Sequence[tuple[float, float]], lo: float,
              hi: float) -> list[tuple[float, float]]:
    """The stretches of [lo, hi] that no interval covers."""
    out = []
    t = lo
    for s, e in sorted(ivs):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def label_at(t: float, spans: Sequence[Interval]) -> str:
    """The innermost host span that covers ``t``, or ``"other"``."""
    best = None
    for sp in spans:
        if sp.start <= t <= sp.end and (best is None or sp.dur < best.dur):
            best = sp
    return best.name if best is not None else "other"


def reduce(host: Sequence[Interval], devices: dict[str, Sequence[Interval]],
           window: Optional[tuple[float, float]] = None) -> TraceSummary:
    """The reduction itself, on plain intervals (seconds on one clock)."""
    if window is None:
        w = [iv for iv in host if iv.name == WINDOW]
        if not w:
            raise ValueError(f"no {WINDOW!r} span in the trace")
        window = (w[0].start, w[0].end)
    lo, hi = window
    labelled = sorted((iv for iv in host if iv.name in HOST_LABELS),
                      key=lambda iv: iv.start)
    op_s: dict[str, float] = {}
    busy = []
    first = None
    for dev in sorted(devices):
        ivs = [iv for iv in devices[dev] if iv.end > lo and iv.start < hi]
        for iv in leaves(ivs):
            op_s[iv.name] = op_s.get(iv.name, 0.0) + (
                min(iv.end, hi) - max(iv.start, lo))
        busy.append(union_length(((iv.start, iv.end) for iv in ivs), lo, hi))
        if first is None:
            first = ivs
    first = first or []
    gaps = []
    for a, b in idle_gaps([(iv.start, iv.end) for iv in first], lo, hi):
        gaps.append((label_at((a + b) / 2, labelled), b - a))
    return TraceSummary(window_s=hi - lo,
                        busy_s=sum(busy) / max(1, len(busy)),
                        n_devices=len(devices), op_s=op_s, gaps=gaps,
                        ops=sorted(first, key=lambda iv: iv.start))


def leaves(ivs: Sequence[Interval]) -> list[Interval]:
    """The operations that contain no other: a while loop's event spans the
    events of the operations it runs, which are counted instead."""
    out = []
    order = sorted(ivs, key=lambda iv: (iv.start, -iv.end))
    for i, iv in enumerate(order):
        nxt = order[i + 1] if i + 1 < len(order) else None
        if nxt is not None and nxt.start < iv.end and nxt.end <= iv.end:
            continue  # a container
        out.append(iv)
    return out


def op_name(event_name: str, module: str) -> str:
    """``<module>:<instruction>``, e.g. ``jit_step:%fused_sgmv.16``: the
    HLO instruction's name without its text, under its program's name
    without the fingerprint."""
    return module.split("(")[0] + ":" + event_name.split(" = ")[0]


def load(path: str) -> tuple[list[Interval], dict[str, list[Interval]]]:
    """Host spans and device operations of an ``.xplane.pb`` file, in
    seconds on the trace's clock."""
    import jax  # deferred: the reduction itself needs no JAX

    pd = jax.profiler.ProfileData.from_file(path)
    host: list[Interval] = []
    devices: dict[str, list[Interval]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW or ev.name in HOST_LABELS:
                        host.append(Interval(ev.name, ev.start_ns * 1e-9,
                                             ev.end_ns * 1e-9))
        elif DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            mods = sorted((ev.start_ns, ev.end_ns, ev.name)
                          for ev in lines[MODULE_LINE].events) if MODULE_LINE in lines else []
            starts = [m[0] for m in mods]
            ivs = devices.setdefault(plane.name, [])
            for ev in (lines[OP_LINE].events if OP_LINE in lines else ()):
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                mod = mods[i][2] if i >= 0 and mods[i][1] >= ev.end_ns else "?"
                ivs.append(Interval(op_name(ev.name, mod), ev.start_ns * 1e-9,
                                    ev.end_ns * 1e-9))
    return host, devices


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)
