"""libra-trace tests: tracer mechanics, engine instrumentation, TTFT
attribution exactness, cache-decision audit coverage, Chrome/Perfetto
export validity, the disabled-tracer overhead gate, sim parity, and the
report CLI.

The engine acceptance run serves a 32-request multi-LoRA trace with
tracing armed on a deliberately tight HBM pool (256 KB) so demand
evictions actually happen — every one must land in the audit log with the
cost-model score it was chosen by, and every finished request must carry
an additive TTFT attribution that reconciles against its measured TTFT
within 1% (by construction it reconciles exactly).
"""

import json
import random

import pytest

from repro.obs import (
    ATTRIB_CATEGORIES,
    EV_CACHE_DROP,
    EV_CACHE_EVICT,
    EV_CACHE_SWAP_OUT,
    EV_CALIBRATION,
    EV_FINISH,
    EV_SUBMIT,
    EV_TTFT_ATTRIBUTION,
    NULL_TRACER,
    TRACK_CACHE,
    TRACK_ENGINE,
    NullTracer,
    Tracer,
    trace_env_enabled,
)

EVICT_EVENTS = (EV_CACHE_EVICT, EV_CACHE_SWAP_OUT, EV_CACHE_DROP)


# ------------------------------------------------------------- unit: tracer
def test_ring_buffer_caps_and_counts_drops():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.instant(TRACK_ENGINE, "ev", float(i))
    assert len(tr.events) == 4
    assert tr.dropped_events == 6
    # the ring keeps the NEWEST events
    assert [e.ts for e in tr.events] == [6.0, 7.0, 8.0, 9.0]


def test_span_duration_and_counters():
    tr = Tracer()
    tr.span(TRACK_ENGINE, "work", 1.0, 1.5, rid="r1")
    tr.span(TRACK_ENGINE, "clamped", 2.0, 1.0)  # t1 < t0 clamps to 0
    tr.counter("queue_depth", 3.0, waiting=2.0)
    tr.count("cache.evict")
    tr.count("cache.evict", 2)
    evs = list(tr.events)
    assert evs[0].dur == 0.5 and evs[0].args == {"rid": "r1"}
    assert evs[1].dur == 0.0
    assert tr.counts["cache.evict"] == 3


def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    assert isinstance(NULL_TRACER, NullTracer)
    NULL_TRACER.span(TRACK_ENGINE, "x", 0.0, 1.0)
    NULL_TRACER.instant(TRACK_ENGINE, "x", 0.0)
    NULL_TRACER.audit("cache.evict", 0.0, node_id=1)
    NULL_TRACER.count("x")
    assert len(NULL_TRACER.events) == 0
    assert NULL_TRACER.counts == {}


def test_export_chrome_is_valid_trace(tmp_path):
    tr = Tracer()
    tr.span(TRACK_ENGINE, "span", 1.0, 1.25, rid="r")
    tr.instant(TRACK_CACHE, "cache.evict", 2.0, node_id=3)
    tr.counter("queue_depth", 3.0, waiting=1.0)
    doc = tr.export_chrome()
    assert isinstance(doc["traceEvents"], list)
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert {"M", "X", "i", "C"} <= phases
    span = next(e for e in doc["traceEvents"] if e["ph"] == "X")
    assert span["ts"] == 1.0e6 and span["dur"] == 0.25e6  # µs
    inst = next(e for e in doc["traceEvents"] if e["ph"] == "i")
    assert inst["s"] == "t"
    # one tid per track, named via metadata
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {m["args"]["name"] for m in meta} >= {TRACK_ENGINE, TRACK_CACHE}
    # dump() writes the same JSON-serializable document
    path = tmp_path / "t.json"
    tr.dump(str(path))
    assert json.loads(path.read_text())["traceEvents"]


def test_env_arming(monkeypatch):
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    assert trace_env_enabled() is False
    monkeypatch.setenv("REPRO_TRACE", "1")
    assert trace_env_enabled() is True
    from repro.sim import SimConfig

    assert SimConfig().trace is True
    monkeypatch.setenv("REPRO_TRACE", "0")
    assert SimConfig().trace is False


# ------------------------------------------------- engine acceptance run
N_REQUESTS = 32
N_ADAPTERS = 4


def _mk_engine(trace: bool, hbm_bytes: int = 256 << 10, key: int = 7):
    jax = pytest.importorskip("jax")
    from repro import configs
    from repro.serving import EngineConfig, ServingEngine

    cfg = configs.reduced(configs.get("qwen3-0.6b"))
    ecfg = EngineConfig(
        hbm_bytes=hbm_bytes, host_bytes=32 << 20, block_size=4,
        max_batch_slots=4, max_seq_len=96, trace=trace,
    )
    eng = ServingEngine(cfg, ecfg, key=jax.random.PRNGKey(key))
    for i in range(N_ADAPTERS):
        eng.register_adapter(f"lora-{i}")
    return eng


def _mk_trace(n=N_REQUESTS, seed=7):
    from repro.serving import Request

    rng = random.Random(seed)
    reqs = []
    for i in range(n):
        plen = rng.randrange(6, 40)
        prompt = tuple(rng.randrange(10, 200) for _ in range(plen))
        reqs.append(Request(f"t{i}", f"lora-{i % N_ADAPTERS}", prompt,
                            max_new_tokens=rng.randrange(2, 5)))
    return reqs


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """One traced 32-request run on a tight pool: (engine, report, doc)."""
    eng = _mk_engine(trace=True)
    for r in _mk_trace():
        eng.submit(r)
    report = eng.run(max_steps=50_000)
    path = tmp_path_factory.mktemp("trace") / "engine_trace.json"
    eng.export_trace(str(path))
    doc = json.loads(path.read_text())
    return eng, report, doc, str(path)


def test_traced_run_finishes_and_attribution_reconciles(traced_run):
    eng, report, _, _ = traced_run
    assert report.n_finished == N_REQUESTS
    for r in eng.finished:
        att = r.ttft_attribution()
        assert att is not None, r.request_id
        assert set(att) <= set(ATTRIB_CATEGORIES), att
        resid = abs(sum(att.values()) - r.ttft)
        assert resid <= 0.01 * r.ttft + 1e-9, (
            f"{r.request_id}: attribution {sum(att.values()):.6f}s vs "
            f"ttft {r.ttft:.6f}s")


def test_every_eviction_in_audit_log_with_score(traced_run):
    eng, _, _, _ = traced_run
    evs = [e for e in eng.tracer.events if e.name in EVICT_EVENTS]
    assert evs, "tight pool produced no evictions — shrink hbm_bytes"
    for e in evs:
        assert e.args is not None
        assert "node_id" in e.args and "bytes" in e.args and "kind" in e.args
        assert e.args.get("score") is not None, e
    # decision events carry the competing candidates they beat
    decided = [e for e in evs if e.name == EV_CACHE_EVICT]
    assert decided and all("beat" in e.args for e in decided)
    # every audited eviction also bumped the counter registry
    n_out = eng.tracer.counts.get(EV_CACHE_SWAP_OUT, 0)
    n_drop = eng.tracer.counts.get(EV_CACHE_DROP, 0)
    assert n_out + n_drop == sum(
        1 for e in evs if e.name in (EV_CACHE_SWAP_OUT, EV_CACHE_DROP))


def test_exported_json_is_chrome_loadable(traced_run):
    _, _, doc, _ = traced_run
    assert isinstance(doc, dict) and isinstance(doc["traceEvents"], list)
    assert doc["traceEvents"], "empty trace"
    for e in doc["traceEvents"]:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] != "M":  # thread-name metadata has no timestamp
            assert isinstance(e["ts"], (int, float))
    assert doc["otherData"]["droppedEvents"] == 0


def test_calibration_series_covers_every_finished_request(traced_run):
    eng, report, _, _ = traced_run
    assert all(r.ttft_predicted is not None for r in eng.finished)
    n_cal = sum(1 for e in eng.tracer.events if e.name == EV_CALIBRATION)
    assert n_cal == report.n_finished
    n_att = sum(1 for e in eng.tracer.events
                if e.name == EV_TTFT_ATTRIBUTION)
    assert n_att == report.n_finished
    # calibration aggregates surface in the report
    assert report.ttft_pred_mae > 0.0


def test_report_cli_renders_engine_trace(traced_run, capsys):
    _, _, _, path = traced_run
    from repro.obs.report import main as report_main

    assert report_main([path, "--top", "5"]) == 0
    out = capsys.readouterr().out
    for section in ("span histograms", "cache audit", "TTFT attribution",
                    "estimate_ttft calibration"):
        assert section in out
    assert EV_CACHE_SWAP_OUT in out


# ------------------------------------------------------- overhead gate
def test_disabled_tracer_overhead_gate():
    """The blocking CI gate: with tracing off, the engine uses the shared
    NULL_TRACER (no buffers, no events), compiles exactly the same
    programs, and produces token-identical output to a traced engine on
    the same trace — the tracer must observe, never steer."""
    eng_off = _mk_engine(trace=False, hbm_bytes=8 << 20)
    eng_on = _mk_engine(trace=True, hbm_bytes=8 << 20)
    assert eng_off.tracer is NULL_TRACER
    for eng in (eng_off, eng_on):
        for r in _mk_trace(n=12, seed=3):
            eng.submit(r)
        rep = eng.run(max_steps=50_000)
        assert rep.n_finished == 12
    assert len(eng_off.tracer.events) == 0
    assert eng_off.tracer.counts == {}
    assert eng_off.compile_counts() == eng_on.compile_counts()
    toks_off = {r.request_id: r.output_tokens for r in eng_off.finished}
    toks_on = {r.request_id: r.output_tokens for r in eng_on.finished}
    assert toks_off == toks_on
    # disabled requests still do the cheap host-float accounting, but no
    # prediction is sampled (that needs the armed tracer)
    assert all(r.ttft_predicted is None for r in eng_off.finished)


# ------------------------------------------------------------ sim parity
def test_sim_emits_shared_vocabulary_and_exact_attribution(tmp_path):
    from repro import configs
    from repro.data import TraceConfig, generate_trace
    from repro.sim import DeployedModel, ServingSimulator, SimConfig

    trace = generate_trace(TraceConfig(
        scenario="agent", n_loras=10, duration=30.0, mean_qps=1.5, seed=3))
    sim = ServingSimulator(
        DeployedModel(configs.get("llama-7b"), cards=1), trace,
        SimConfig(variant="fastlibra", trace=True, schedule_mode="mixed"))
    res = sim.run()
    assert len(res.finished) == len(trace)
    names = {e.name for e in sim.tracer.events}
    # same vocabulary the engine emits (constants shared via repro.obs)
    assert {EV_SUBMIT, EV_FINISH, EV_TTFT_ATTRIBUTION, EV_CALIBRATION,
            "req.admit", "req.queue", "engine.step",
            "prefill.chunk", "cache.admit"} <= names
    for r in res.finished:
        if r.ttft is None:
            continue
        resid = abs(sum(r.attribution.values()) - r.ttft)
        assert resid <= 0.01 * r.ttft + 1e-9, (r.rid, r.attribution, r.ttft)
    path = tmp_path / "sim_trace.json"
    sim.export_trace(str(path))
    doc = json.loads(path.read_text())
    assert doc["traceEvents"]


def test_sim_untraced_uses_null_tracer():
    from repro import configs
    from repro.data import TraceConfig, generate_trace
    from repro.sim import DeployedModel, ServingSimulator, SimConfig

    trace = generate_trace(TraceConfig(
        scenario="chatbot", n_loras=5, duration=10.0, mean_qps=1.0, seed=1))
    sim = ServingSimulator(
        DeployedModel(configs.get("llama-7b"), cards=1), trace,
        SimConfig(variant="fastlibra", trace=False))
    sim.run()
    assert sim.tracer is NULL_TRACER
    assert len(sim.tracer.events) == 0
