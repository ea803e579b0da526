"""Engine step phases on the profiler's clock (``repro.obs.phase``).

One run of a small multi-LoRA workload, built so that every phase runs
(repeated prompt openings for prefix gathers, a tight HBM pool for swaps, a
clock that makes the swapper due every few steps), with libra-trace armed
and under a ``jax.profiler`` session, each ``engine.step`` call wrapped in an
annotation of that name as the benchmark harness wraps it. The profiler's
host plane must hold every phase nested in a step, ``step.dispatch`` must
name the shape it ran, JAX's compiles must nest in the phase that triggered
them, and libra-trace's Chrome export must hold the same phases.
"""

import glob
import itertools
import random

import pytest

jax = pytest.importorskip("jax")

from repro.obs import NULL_TRACER, TRACK_ENGINE, Tracer  # noqa: E402
from repro.obs.phase import PH_DISPATCH, PHASES, phase  # noqa: E402

STEP = "engine.step"
COMPILES = {"backend_compile_and_load", "lower_sharding_computation"}


def _serve(step, submit):
    from repro.serving import Request

    rng = random.Random(3)
    openings = [tuple(rng.randrange(10, 200) for _ in range(20)) for _ in range(3)]
    for i in range(12):
        prompt = openings[i % 3] + tuple(
            rng.randrange(10, 200) for _ in range(rng.randrange(2, 8)))
        submit(Request(f"r{i}", f"lora-{i % 3}", prompt, max_new_tokens=3))
        for _ in range(3):
            step()


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """(engine, host events as (name, start_ns, end_ns, stats))."""
    from repro import configs
    from repro.serving import EngineConfig, ServingEngine

    ticks = itertools.count()
    cfg = configs.reduced(configs.get("qwen3-0.6b"))
    ecfg = EngineConfig(
        hbm_bytes=256 << 10, host_bytes=32 << 20, block_size=4,
        max_batch_slots=4, max_seq_len=96, trace=True,
        clock=lambda: next(ticks) * 1e-3)  # swapper due every 100 reads
    eng = ServingEngine(cfg, ecfg, key=jax.random.PRNGKey(7))
    for i in range(4):
        eng.register_adapter(f"lora-{i}")

    def step():
        with jax.profiler.TraceAnnotation(STEP):
            eng.step()

    log_dir = tmp_path_factory.mktemp("profile")
    opts = jax.profiler.ProfileOptions()  # the benchmark harness's levels
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        _serve(step, eng.submit)
        while eng.waiting or any(r is not None for r in eng._slot_req):
            step()
    assert len(eng.finished) == 12
    path = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    events = [(ev.name, ev.start_ns, ev.end_ns,
               dict(ev.stats) if ev.name == PH_DISPATCH else {})
              for plane in pd.planes if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events]
    return eng, events


def _within(inner, outers) -> bool:
    return any(s <= inner[1] and inner[2] <= e for _, s, e, _ in outers)


def test_every_phase_runs_nested_in_its_step(profiled):
    _, events = profiled
    steps = [ev for ev in events if ev[0] == STEP]
    phases = [ev for ev in events if ev[0] in PHASES]
    assert {ev[0] for ev in phases} == set(PHASES)
    for ev in phases:
        assert _within(ev, steps), ev


def test_dispatch_names_its_shape_and_holds_its_compiles(profiled):
    _, events = profiled
    dispatches = [ev for ev in events if ev[0] == PH_DISPATCH]
    kinds = {ev[3]["kind"] for ev in dispatches}
    assert kinds == {"mixed", "decode", "catchup"}
    for _, _, _, stats in dispatches:
        assert stats["bucket"] >= 1 and stats["rows"] >= 1
        if stats["kind"] != "mixed":
            assert stats["bucket"] == 1
    compiles = [ev for ev in events if ev[0] in COMPILES]
    # the first call of each step program compiles inside its dispatch
    assert any(_within(c, dispatches) for c in compiles)
    # and every compile of the run lies inside some phase of a step
    phases = [ev for ev in events if ev[0] in PHASES]
    assert compiles and all(_within(c, phases) for c in compiles)


def test_armed_tracer_exports_the_same_phases(profiled):
    eng, events = profiled
    doc = eng.tracer.export_chrome()
    tid = next(e["tid"] for e in doc["traceEvents"]
               if e["ph"] == "M" and e["args"]["name"] == TRACK_ENGINE)
    exported = sorted(e["name"] for e in doc["traceEvents"]
                      if e["ph"] == "X" and e["tid"] == tid and e["name"] in PHASES)
    assert exported == sorted(ev[0] for ev in events if ev[0] in PHASES)
    spans = [e for e in doc["traceEvents"] if e.get("name") == PH_DISPATCH]
    assert all({"kind", "bucket", "rows"} <= set(e["args"]) for e in spans)
    # the cache.* phases are spans: the report's audit summary skips them
    from repro.obs.report import audit_summary

    counts = audit_summary(doc["traceEvents"])["counts"]
    assert counts and not set(counts) & set(PHASES)


def test_phase_is_a_bare_annotation_while_the_tracer_is_off():
    scope = phase(NULL_TRACER, lambda: 0.0, PH_DISPATCH, kind="decode")
    assert isinstance(scope, jax.profiler.TraceAnnotation)
    with scope:
        pass
    assert len(NULL_TRACER.events) == 0
    clock = iter([1.0, 1.5])
    tr = Tracer()
    with phase(tr, lambda: next(clock), PH_DISPATCH, kind="decode", rows=2):
        pass
    (ev,) = tr.events
    assert (ev.name, ev.track, ev.ts, ev.dur) == (PH_DISPATCH, TRACK_ENGINE, 1.0, 0.5)
    assert ev.args == {"kind": "decode", "rows": 2}
