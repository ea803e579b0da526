"""The device's idle time inside the engine's step, split by what the host
was doing there: the program's own phases (``step.*`` and ``cache.*``, from
``repro.obs.phase``) and JAX's compile annotations, read from the same
profiler trace that ``tracered`` reduces.

``tracered`` keeps the harness's own spans only, so it labels nearly every
idle gap ``engine.step``. Here each gap is labelled by the innermost of the
harness spans, the phases and the compiles (a sorted sweep that gives
``tracered.label_at``'s labels), and the idle time is split by exact
intersection: under a compile, and under a phase outside any compile. Busy
time, per-operation time and the gaps themselves are ``tracered``'s."""

from __future__ import annotations

import bisect
import dataclasses
import heapq
from typing import Iterable, Sequence

from . import tracered
from .tracered import Interval

PHASE_PREFIXES = ("step.", "cache.")
# JAX's compile path as its own profiler annotations name it: tracing to a
# jaxpr, lowering, and XLA's compile and load (jax/_src/interpreters and
# jax/_src/compiler.py).
COMPILE_SPANS = frozenset({
    "trace_to_jaxpr_dynamic", "trace_to_jaxpr_nounits",
    "lower_sharding_computation", "lower_parallel_callable",
    "backend_compile", "backend_compile_and_load"})
# A jit call's own span. One that holds a compile-path annotation missed the
# in-memory cache and counts whole as compile time: it also covers loading
# the executable from the persistent cache, which JAX does not annotate.
JIT_CALL = "PjitFunction("

Span = tuple[float, float]


@dataclasses.dataclass
class Split:
    window_s: float
    idle_s: float
    compile_s: float  # idle under a compile span
    host_s: float  # idle under a phase span and under no compile span
    gaps: list[tuple[str, float]]  # (innermost label, seconds), one per gap
    # (innermost phase, seconds), one per gap: where a compile came from
    phase_gaps: list[tuple[str, float]]
    phase_s: dict[str, tuple[int, float]]  # name -> (spans, seconds in window)

    def gaps_by_label(self) -> list[tuple[str, float]]:
        return by_label(self.gaps)

    def gaps_by_phase(self) -> list[tuple[str, float]]:
        return by_label(self.phase_gaps)

    def compile_share(self) -> float:
        """Percent of the window the device idled under a compile."""
        return 100.0 * self.compile_s / self.window_s

    def host_share(self) -> float:
        """Percent of the window the device idled under a host phase of the
        step, compiles excluded."""
        return 100.0 * self.host_s / self.window_s


def by_label(pairs: Iterable[tuple[str, float]]) -> list[tuple[str, float]]:
    agg: dict[str, float] = {}
    for label, s in pairs:
        agg[label] = agg.get(label, 0.0) + s
    return sorted(agg.items(), key=lambda kv: -kv[1])


def is_phase(name: str) -> bool:
    return name.startswith(PHASE_PREFIXES)


def load(path: str) -> tuple[list[Interval], list[Interval]]:
    """The phase spans and the compile spans (the annotations, and the jit
    calls that hold one on the same thread) of an ``.xplane.pb`` file, in
    seconds on the trace's clock (``tracered.load`` reads the rest)."""
    import jax  # deferred: the reduction itself needs no JAX

    pd = jax.profiler.ProfileData.from_file(path)
    phases: list[Interval] = []
    compiles: list[Interval] = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            found: list[Interval] = []
            calls: list[Interval] = []
            for ev in line.events:
                if is_phase(ev.name):
                    out = phases
                elif ev.name in COMPILE_SPANS:
                    out = found
                elif ev.name.startswith(JIT_CALL):
                    out = calls
                else:
                    continue
                out.append(Interval(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9))
            compiles += found + missed_calls(calls, found)
    return phases, compiles


def missed_calls(calls: Sequence[Interval],
                 found: Sequence[Interval]) -> list[Interval]:
    """The jit calls of one thread within which a compile annotation of the
    same thread starts: on one thread, spans nest."""
    starts = sorted(iv.start for iv in found)
    out = []
    for c in calls:
        i = bisect.bisect_left(starts, c.start)
        if i < len(starts) and starts[i] <= c.end:
            out.append(c)
    return out


def labels_at(points: Sequence[float], spans: Sequence[Interval]) -> list[str]:
    """``tracered.label_at`` for many points in one sorted sweep: for each
    point the shortest span that covers it (ends included), the earliest in
    ``spans`` among equally short ones, or ``"other"``."""
    by_start = sorted(range(len(spans)), key=lambda i: spans[i].start)
    out = ["other"] * len(points)
    heap: list[tuple[float, int]] = []  # (duration, index) of open spans
    j = 0
    for q in sorted(range(len(points)), key=points.__getitem__):
        t = points[q]
        while j < len(by_start) and spans[by_start[j]].start <= t:
            i = by_start[j]
            heapq.heappush(heap, (spans[i].dur, i))
            j += 1
        while heap and spans[heap[0][1]].end < t:
            heapq.heappop(heap)  # points only grow: an ended span stays ended
        if heap:
            out[q] = spans[heap[0][1]].name
    return out


def merged(ivs: Iterable[Span]) -> list[Span]:
    """The union of intervals as sorted, disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(ivs):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(a: Sequence[Span], b: Sequence[Span]) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def minus(a: Sequence[Span], b: Sequence[Span]) -> list[Span]:
    """``a`` without ``b``, both sorted and disjoint."""
    out: list[Span] = []
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def split(host: Sequence[Interval], devices: dict[str, Sequence[Interval]],
          phases: Sequence[Interval], compiles: Sequence[Interval]) -> Split:
    """The window's idle time split by the host's phases and compiles; the
    window and the idle gaps are ``tracered.reduce``'s."""
    w = [iv for iv in host if iv.name == tracered.WINDOW]
    if not w:
        raise ValueError(f"no {tracered.WINDOW!r} span in the trace")
    lo, hi = w[0].start, w[0].end
    s = tracered.reduce(host, devices, (lo, hi))
    gaps = tracered.idle_gaps([(iv.start, iv.end) for iv in s.ops], lo, hi)
    spans = sorted([iv for iv in host if iv.name in tracered.HOST_LABELS]
                   + list(phases) + list(compiles), key=lambda iv: iv.start)
    mids = [(a + b) / 2 for a, b in gaps]
    labels = labels_at(mids, spans)
    in_phase = labels_at(mids, sorted(phases, key=lambda iv: iv.start))
    comp = merged((iv.start, iv.end) for iv in compiles)
    host_only = minus(merged((iv.start, iv.end) for iv in phases), comp)
    phase_s: dict[str, tuple[int, float]] = {}
    for iv in phases:
        d = min(iv.end, hi) - max(iv.start, lo)
        if d > 0:
            n, t = phase_s.get(iv.name, (0, 0.0))
            phase_s[iv.name] = (n + 1, t + d)
    return Split(window_s=hi - lo, idle_s=sum(b - a for a, b in gaps),
                 compile_s=overlap(gaps, comp), host_s=overlap(gaps, host_only),
                 gaps=[(lb, b - a) for lb, (a, b) in zip(labels, gaps)],
                 phase_gaps=[(lb, b - a) for lb, (a, b) in zip(in_phase, gaps)],
                 phase_s=phase_s)


def split_file(path: str) -> Split:
    host, devices = tracered.load(path)
    phases, compiles = load(path)
    return split(host, devices, phases, compiles)
