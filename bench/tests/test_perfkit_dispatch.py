"""The dispatch record that ``step_mfu`` and the kernel rooflines rest on:
``driver.Harness._record`` rebuilds each dispatch's rows from outside the
engine after every step. The engine records the same dispatches from inside
when libra-trace is armed: one ``prefill.chunk`` span per chunk row and one
``decode.step`` span per decode row, all rows of one call sharing its start
and end. Grouped by call, the two records must agree, on the batch mix and
on the staged chat mix (prefix hits, so chunks start past zero)."""

import dataclasses

import pytest

from perfkit import driver, loadgen, spec
from repro.obs import EV_DECODE_STEP, EV_PREFILL_CHUNK
from test_perfkit_harness import tiny, tiny_mix

SEED = 20260418 + 2**32


def calls_from_spans(events, requests) -> list[tuple[str, list]]:
    """The engine's own record as (kind, sorted rows) per call, in order."""
    calls: dict[tuple[float, float], list] = {}
    for ev in events:
        if ev.name in (EV_PREFILL_CHUNK, EV_DECODE_STEP):
            calls.setdefault((ev.ts, ev.ts + ev.dur), []).append(ev)
    decoded: dict[str, int] = {}
    out = []
    for key in sorted(calls):
        rows, kind = [], "decode"
        for ev in calls[key]:
            rid = ev.args["rid"]
            r = requests[rid]
            if ev.name == EV_PREFILL_CHUNK:
                kind = "mixed"
                pos, n = ev.args["pos"], ev.args["tokens"]
                rows.append((pos, n, pos + n >= len(r.prompt), r.adapter_id))
            else:
                # the k-th decode call of a request feeds position prompt + k
                k = decoded.get(rid, 0)
                decoded[rid] = k + 1
                rows.append((len(r.prompt) + k, 1, True, r.adapter_id))
        out.append((kind, sorted(rows)))
    return out


@pytest.mark.parametrize("mix_name", ["batch-unique", "chat-lora32"])
def test_harness_dispatch_record_matches_the_engines(monkeypatch, mix_name):
    real = driver.engine_config
    monkeypatch.setattr(driver, "engine_config",
                        lambda model, cfg, s: dataclasses.replace(real(model, cfg, s),
                                                                  trace=True))
    bench = spec.load_benchmark()
    cfg = tiny(spec.find_config(bench, "qwen3-0.6b"))
    mix = tiny_mix(spec.load_mix(mix_name))
    engine, shape, _, _ = driver.build(spec.model_module(cfg), cfg, SEED,
                                       int(mix["adapters_registered"]))
    assert engine.tracer.enabled
    block = cfg["serving"]["engine"]["block_size"]
    driver.warm_shapes(engine, engine.prefill.buckets, block)
    engine.tracer.clear()
    t0 = engine.now()
    traffic = loadgen.make_traffic(mix, shape.vocab, SEED, 30.0, t0)
    h = driver.Harness(engine, traffic, block)
    h.record_calls = True
    h.run_until(t0 + 2.5)
    assert engine.tracer.dropped_events == 0
    seen = [(c.kind, sorted(c.rows)) for c in h.calls]
    assert len(seen) > 20 and {k for k, _ in seen} == {"mixed", "decode"}
    assert seen == calls_from_spans(engine.tracer.events, h.requests)
    if mix_name == "chat-lora32":
        assert any(r.matched_tokens > 0 for r in h.requests.values())
