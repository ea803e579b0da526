"""The split of the device's idle time by the engine's step phases and JAX's
compiles (``perfkit/phases.py``): its interval arithmetic on synthetic
spans, its sweep labeller against ``tracered.label_at``, and the traces
recorded on a TPU v5e. ``data/batch-unique.xplane.pb`` predates the phases
and must reduce as it always did; ``data/batch-unique.phases.xplane.pb``
(bench/tools/record_trace.py, then bench/tools/phase_breakdown.py --prune)
holds them."""

import pathlib
import random

import pytest

from perfkit import phases, tracered
from perfkit.tracered import Interval

DATA = pathlib.Path(__file__).parent / "data"
OLD = DATA / "batch-unique.xplane.pb"
NEW = DATA / "batch-unique.phases.xplane.pb"

DEV = {"/device:TPU:0": [Interval("jit_step:%fused_sgmv.1", 1.0, 2.0),
                         Interval("jit_step:%fused_sgmv.2", 5.0, 6.0)]}
HOST = [Interval(tracered.WINDOW, 0.0, 10.0),
        Interval("engine.step", 0.0, 4.5),
        Interval("bench.record", 4.5, 5.5),
        Interval("engine.step", 5.5, 10.0)]
PHASES = [Interval("step.admit", 0.2, 0.8),
          Interval("step.finish", 2.5, 4.0),
          Interval("cache.scatter", 3.0, 4.0),  # nested in step.finish
          Interval("step.readback", 7.0, 8.5),
          Interval("step.plan", 8.0, 9.0)]  # overlaps step.readback
COMPILES = [Interval("backend_compile_and_load", 3.2, 3.8),  # in cache.scatter
            Interval("lower_sharding_computation", 8.2, 8.4)]


def test_interval_arithmetic():
    a = phases.merged([(3.0, 4.0), (0.0, 1.0), (0.5, 2.0), (5.0, 5.0)])
    assert a == [(0.0, 2.0), (3.0, 4.0)]
    assert phases.overlap(a, [(1.5, 3.5)]) == pytest.approx(1.0)
    assert phases.minus(a, [(0.5, 1.0), (1.5, 3.5)]) == [
        (0.0, 0.5), (1.0, 1.5), (3.5, 4.0)]
    assert phases.minus(a, []) == a and phases.minus([], a) == []


def test_jit_call_holding_a_compile_counts_whole():
    calls = [Interval("PjitFunction(scatter)", 1.0, 3.0),
             Interval("PjitFunction(add)", 4.0, 4.1),
             Interval("PjitFunction(_take)", 5.0, 7.0)]
    found = [Interval("lower_sharding_computation", 1.2, 1.5),
             Interval("backend_compile_and_load", 6.0, 6.5)]
    assert [c.name for c in phases.missed_calls(calls, found)] == [
        "PjitFunction(scatter)", "PjitFunction(_take)"]
    assert phases.missed_calls(calls, []) == []


def test_split_by_exact_intersection():
    """Idle is [0,1], [2,5] and [6,10]. Under compiles: 0.6 in the one
    nested in cache.scatter, 0.2 in the one inside both overlapping phases.
    Under phases and no compile: 0.6 (admit) + 0.7 + 0.2 (finish around the
    compile) + 1.2 + 0.6 (readback and plan around the other). The idle
    stretch under bench.record and the idle under engine.step with no phase
    belong to neither."""
    sp = phases.split(HOST, DEV, PHASES, COMPILES)
    assert sp.window_s == 10.0 and sp.idle_s == pytest.approx(8.0)
    assert sp.compile_s == pytest.approx(0.8)
    assert sp.host_s == pytest.approx(3.3)
    assert sp.compile_share() == pytest.approx(8.0)
    assert sp.host_share() == pytest.approx(33.0)
    assert sp.compile_s + sp.host_s <= sp.idle_s
    # each gap is labelled by the innermost span over its middle
    assert [lb for lb, _ in sp.gaps] == [
        "step.admit", "backend_compile_and_load", "step.plan"]
    # and by the innermost phase, which names where a compile came from
    assert [lb for lb, _ in sp.phase_gaps] == [
        "step.admit", "cache.scatter", "step.plan"]
    assert sp.phase_s["cache.scatter"] == (1, pytest.approx(1.0))
    # without phases it labels as tracered does and splits nothing
    bare = phases.split(HOST, DEV, [], [])
    summary = tracered.reduce(HOST, DEV)
    assert bare.gaps == summary.gaps and bare.compile_s == bare.host_s == 0.0


def test_sweep_labels_equal_the_scan():
    rng = random.Random(11)
    for _ in range(300):
        spans = []
        for k in range(rng.randrange(0, 12)):
            s = float(rng.randrange(0, 40))
            spans.append(Interval(f"s{k}", s, s + rng.randrange(0, 12)))
        points = [rng.randrange(-2, 55) / 1.0 for _ in range(rng.randrange(1, 30))]
        points += [iv.start for iv in spans] + [iv.end for iv in spans]
        assert phases.labels_at(points, spans) == [
            tracered.label_at(t, spans) for t in points]


def test_recorded_trace_without_phases_reduces_as_before():
    host, devs = tracered.load(str(OLD))
    s = tracered.reduce(host, devs)
    assert s.busy_s == pytest.approx(0.3676334990000023, abs=1e-12)
    assert len(s.op_s) == 261
    assert sum(s.op_s.values()) == pytest.approx(0.3660075250000152, abs=1e-12)
    assert len(s.gaps) == 630
    assert sum(g for _, g in s.gaps) == pytest.approx(0.036115730999997764, abs=1e-12)
    found, compiles = phases.load(str(OLD))
    assert found == [] and compiles == []
    sp = phases.split(host, devs, found, compiles)
    assert sp.gaps == s.gaps
    assert sp.idle_s == pytest.approx(s.window_s - s.busy_s, rel=1e-9)


def test_recorded_trace_with_phases():
    """A 0.59 s traced window of the batch cell on a TPU v5e (seed
    3413000011): one finished request's commit compiles its per-length
    copies; the rest of the idle time is the readback's transfer."""
    host, devs = tracered.load(str(NEW))
    found, compiles = phases.load(str(NEW))
    s = tracered.reduce(host, devs)
    sp = phases.split(host, devs, found, compiles)
    assert sp.window_s == s.window_s == pytest.approx(0.585707787)
    assert sp.idle_s == pytest.approx(s.window_s - s.busy_s, rel=1e-9)
    assert sum(g for _, g in sp.gaps) == pytest.approx(sum(g for _, g in s.gaps), rel=1e-12)
    assert {iv.name for iv in found} == {
        "step.admit", "step.swapper", "step.plan", "step.dispatch", "step.merge",
        "step.readback", "step.finish", "cache.scatter"}
    names = {iv.name for iv in compiles}
    assert {"backend_compile_and_load", "lower_sharding_computation"} <= names
    assert any(n.startswith(phases.JIT_CALL) for n in names)
    assert sp.compile_share() == pytest.approx(47.312721147072615, rel=1e-9)
    assert sp.host_share() == pytest.approx(5.610887498751557, rel=1e-9)
    idle_share = 100.0 * sp.idle_s / sp.window_s
    assert sp.compile_share() + sp.host_share() >= 0.9 * idle_share
    # tracered puts it all under engine.step; the phases take it over
    assert dict(s.gaps_by_label())["engine.step"] > 0.99 * sp.idle_s
    labels = dict(sp.gaps_by_label())
    assert labels.get("engine.step", 0.0) < 0.1 * sp.idle_s
    assert labels["backend_compile_and_load"] > 0.4 * sp.idle_s
    assert sp.gaps_by_phase()[0][0] == "cache.scatter"
