"""One run of one cell: build the engine, warm it, drive the traffic through
a timed window, check what it served against the plain reference, and read
the metrics.

The program is reached only through its API: ``repro.configs.get`` (in a
model module's ``program_config``), ``EngineConfig``, ``ServingEngine`` (``register_adapter``, ``submit``,
``step``, ``now``, ``compile_counts``, ``manager.stats``, the public
``params`` and ``adapters`` it serves from) and ``Request``. The weights are
the benchmark's own, drawn by the configuration's model module
(``bench/models/``, ``spec.model_module``) and installed in place of the
engine's."""

from __future__ import annotations

import dataclasses
import gc
import os
import pathlib
import shutil
import sys
import time
import types
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import loadgen, reference, spec, tracered, weights, window

# the reference compares at most this many served tokens of at most this many
# requests; served positions are padded to PAD_SERVED per request
SAMPLE_TOKENS = 600
TRACE_S = 10.0
SAMPLE_REQUESTS = 16
PAD_SERVED = 256


class NoAccelerator(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_accelerator(chips: int) -> list:
    """The devices the cell runs on; raises without a TPU or with fewer
    chips than the cell asks for."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU found (JAX sees {devs[0].platform})")
    if len(devs) < chips:
        raise NoAccelerator(f"cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def enable_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``,
    else ``<checkout>/.jax_cache`` (a fixed path: the path is part of the
    cache key). JAX's own thresholds stay: the engine's many small eager
    programs compile in tens of milliseconds, and writing each to the cache
    cost ten times that on the chip's machine."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileCounter:
    """Counts compile requests and persistent-cache hits as JAX reports
    them."""

    def __init__(self) -> None:
        self.compiles = 0
        self.cache_hits = 0
        self.seconds = 0.0
        self.names: dict[str, list] = {}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, name: str, secs: float, **kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs
            n = self.names.setdefault(str(kw.get("fun_name", "?")), [0, 0.0])
            n[0] += 1
            n[1] += secs

    def _ev(self, name: str, **kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def read(self) -> tuple[int, int, float, dict]:
        return (self.compiles, self.cache_hits, self.seconds,
                {k: tuple(v) for k, v in self.names.items()})


# ---------------------------------------------------------------- the engine
def engine_config(model: types.ModuleType, cfg: dict, s):
    """The configuration's engine settings, its pool tiers sized in tokens
    and adapters by the model's per-token and per-adapter bytes."""
    from repro.serving import EngineConfig

    e = dict(cfg["serving"]["engine"])
    pool = e.pop("pool")
    kv, ad = model.kv_bytes_per_token(s), model.adapter_bytes(s)
    return EngineConfig(
        hbm_bytes=pool["hbm_tokens"] * kv + pool["hbm_adapters"] * ad,
        host_bytes=pool["host_tokens"] * kv + pool["host_adapters"] * ad,
        trace=False, **e)


# --------------------------------------------------------------- the run
@dataclasses.dataclass
class Call:
    """One dispatch of the model as the harness saw it from outside: rows
    of (start position, real tokens, emits logits, adapter)."""

    kind: str  # "mixed" (a prefill chunk rode along) | "decode"
    rows: list[tuple[int, int, bool, str]]


@dataclasses.dataclass
class RunState:
    """What a run leaves for the metric readers."""

    cell: dict
    cfg: dict
    mix: dict
    model: types.ModuleType  # bench/models/<model_type>.py
    shape: object  # model.Shape
    device_kind: str
    traced: bool
    obs: dict[str, window.Obs]
    w0: float
    w1: float
    stats0: dict
    stats1: dict
    calls: list[Call] = dataclasses.field(default_factory=list)
    trace: Optional[tracered.TraceSummary] = None
    # seconds JAX spent compiling between the window's open and close
    compile_s: Optional[float] = None

    @property
    def seconds(self) -> float:
        return self.w1 - self.w0

    def stat_delta(self, key: str) -> int:
        return self.stats1[key] - self.stats0[key]


class Harness:
    """Drives one engine with one traffic mix on the engine's clock."""

    def __init__(self, engine, traffic: loadgen.Traffic, block_size: int):
        self.engine = engine
        self.traffic = traffic
        self.block = block_size
        # host spans on the profiler's clock (no-ops while no trace runs)
        self.ann = jax.profiler.TraceAnnotation
        self.obs: dict[str, window.Obs] = {}
        self.requests: dict[str, object] = {}  # key -> Request
        self.inflight: dict[str, object] = {}
        self.asks: dict[str, loadgen.Ask] = {}
        self.failed: list[str] = []
        self.record_calls = False
        self.calls: list[Call] = []
        self._prev: dict[str, tuple] = {}

    def submit_due(self, now: float) -> None:
        from repro.serving import Request

        with self.ann("loadgen"):
            asks = self.traffic.pop_due(now)
        for a in asks:
            r = Request(a.key, a.adapter, a.prompt, a.max_new, submit_time=a.due,
                        priority=a.priority, deadline=a.deadline,
                        shared_prefix_len=a.shared_prefix_len)
            with self.ann("engine.submit"):
                self.engine.submit(r)
            self.obs[a.key] = window.Obs(
                key=a.key, due=a.due, submitted=self.engine.now(),
                prompt_len=len(a.prompt), history_len=a.history_len,
                max_new=a.max_new)
            self.requests[a.key] = r
            self.inflight[a.key] = r
            self.asks[a.key] = a
            self._prev[a.key] = (0, 0, False, False)

    def step(self) -> None:
        if not self.inflight:
            nd = self.traffic.next_due()
            wait = 0.002 if nd is None else nd - self.engine.now()
            if wait > 0:
                with self.ann("bench.idle"):
                    time.sleep(min(wait, 0.002))
        with self.ann("engine.step"):
            self.engine.step()
        t = self.engine.now()
        with self.ann("bench.record"):
            self._record(t)

    def _record(self, t: float) -> None:
        """After a step: stamp each new output token with ``t``, note
        admissions and finishes, and (in the window) rebuild the step's
        dispatches from each row's progress. A row whose prefill advanced
        rode the mixed call with the decode rows; a row that finished its
        prefill and got a second token had it from the catch-up decode call;
        without any prefill row the step was one decode call."""
        mixed_rows, catchup, decode_rows = [], [], []
        for key, r in list(self.inflight.items()):
            o = self.obs[key]
            pos0, n0, dec0, adm0 = self._prev[key]
            out = r.output_tokens
            n = len(out)
            if n > n0:
                o.tokens.extend([t] * (n - n0))
            admitted = r.admit_time is not None
            if admitted and o.admitted is None:
                o.admitted = r.admit_time
            if self.record_calls and admitted:
                base = pos0 if adm0 else r.matched_tokens
                chunk = r.prefill_pos - base if r.prefill_pos > base else 0
                plen = len(r.prompt)
                done = chunk > 0 and r.prefill_pos >= plen
                if chunk > 0:
                    mixed_rows.append((base, chunk, done, r.adapter_id))
                extra = n - n0 - (1 if done else 0)
                if dec0 and n > n0:
                    decode_rows.append((plen + n0 - 1, 1, True, r.adapter_id))
                elif done and extra > 0:
                    catchup.append((plen, 1, True, r.adapter_id))
            self._prev[key] = (r.prefill_pos, n, r.phase.name == "DECODE",
                               admitted)
            phase = r.phase.name
            if phase == "FINISHED":
                o.finished = t
                del self.inflight[key]
                self.traffic.finished(key, tuple(out), t)
            elif phase == "ABORTED":
                self.failed.append(key)
                del self.inflight[key]
        if self.record_calls:
            if mixed_rows:
                self.calls.append(Call("mixed", mixed_rows + decode_rows))
                if catchup:
                    self.calls.append(Call("decode", catchup))
            elif decode_rows:
                self.calls.append(Call("decode", decode_rows))

    def run_until(self, t_end: float) -> None:
        """Submit and step until the engine clock reaches ``t_end``."""
        while True:
            now = self.engine.now()
            if now >= t_end:
                return
            self.submit_due(now)
            self.step()

    # -------------------------------------------------------- correctness
    def kinds_of(self, key: str) -> set[str]:
        """How the request's prompt and adapter were served."""
        r, o = self.requests[key], self.obs[key]
        k = set()
        if r.hbm_hit_tokens > 0:
            k.add("hbm_prefix")
        if r.matched_tokens > r.hbm_hit_tokens:
            k.add("host_prefix")
        if r.lora_coldstart > 0:
            k.add("adapter_loaded")
        cached = (o.history_len // self.block) * self.block
        if o.history_len and r.matched_tokens < cached:
            k.add("recomputed")
        if any(n % self.block == 0 and r.matched_tokens >= n
               for n in self.turn_ends(key)):
            # an earlier turn of the session ended on a block boundary and
            # this request reused that block from the cache: its last
            # position holds the KV of a token the engine never ran through
            # the model (PERF.md, Open questions)
            k.add("boundary_tail")
        return k

    def turn_ends(self, key: str) -> list[int]:
        """Lengths (prompt + served reply) at which the earlier turns of
        ``key``'s session ended."""
        a = self.asks[key]
        if a.session < 0:
            return []
        return [self.obs[k].history_len for k, b in self.asks.items()
                if b.session == a.session and 0 < b.turn <= a.turn]


def pick_sample(h: Harness, w0: float, w1: float, seed: int) -> list[str]:
    """Requests finished in the window to check: the longest, one of each
    kind that occurs, then others drawn from the seed, up to the caps."""
    done = sorted(k for k, o in h.obs.items()
                  if o.finished is not None and w0 <= o.finished <= w1)
    if not done:
        return []
    rng = np.random.default_rng([seed % (1 << 63), 99])
    size = lambda k: h.obs[k].prompt_len + len(h.requests[k].output_tokens)
    pick = [max(done, key=size)]
    for kind in ("hbm_prefix", "host_prefix", "adapter_loaded", "recomputed",
                 "boundary_tail"):
        have = [k for k in done if kind in h.kinds_of(k)]
        if have and not any(kind in h.kinds_of(k) for k in pick):
            pick.append(have[int(rng.integers(len(have)))])
    rest = [k for k in rng.permutation(done) if k not in pick]
    tokens = sum(len(h.requests[k].output_tokens) for k in pick)
    for k in rest:
        if len(pick) >= SAMPLE_REQUESTS or tokens >= SAMPLE_TOKENS:
            break
        pick.append(str(k))
        tokens += len(h.requests[k].output_tokens)
    return pick


def _logits(model: types.ModuleType, params, adapters_dev, c, seq, pad_len: int,
            precision: str) -> np.ndarray:
    """The reference's logits at each served position of ``seq``."""
    ai, prompt, served, shared = seq
    toks = np.zeros((pad_len,), np.int32)
    full = list(prompt) + list(served)
    toks[: len(full)] = full
    at = np.zeros((PAD_SERVED,), np.int32)
    n = len(served)
    at[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    ad = {t: (a[ai], b[ai]) for t, (a, b) in adapters_dev.items()}
    return np.asarray(model.logits_at(
        params, ad, jnp.asarray(toks), jnp.asarray(at), jnp.int32(shared),
        c=c, precision=precision))[:n]


def compare(model: types.ModuleType, params, adapters_dev, c, seqs,
            pad_len: int) -> list[np.ndarray]:
    """Per sampled request, the gap of each served token below the oracle's
    best (``model.logits_at`` with ``c = model.Consts``). ``seqs``: (adapter
    index, prompt, served, leading tokens served without the adapter)."""
    return [reference.served_gaps(_logits(model, params, adapters_dev, c, q,
                                          pad_len, "highest"), np.asarray(q[2]))
            for q in seqs]


def control_seqs(model: types.ModuleType, params, adapters_dev, c, seqs,
                 pad_len: int, precision: str) -> list[tuple]:
    """The control in the program's place: ``seqs`` with each served token
    replaced by the token the lower-precision reference puts first at the
    same position, to be judged exactly as the program is."""
    return [(ai, prompt, tuple(int(t) for t in _logits(
                model, params, adapters_dev, c, (ai, prompt, served, shared),
                pad_len, precision).argmax(-1)), shared)
            for ai, prompt, served, shared in seqs]


def judge(gaps: list[np.ndarray], seqs, failed: int, limits: dict,
          vocab: int) -> tuple[bool, dict]:
    """``correct`` and the numbers it compared, each beside its limit."""
    widest = float(max((g.max() for g in gaps if len(g)), default=float("inf")))
    compared = int(sum(len(g) for g in gaps))
    limit = float(limits["max_logit_gap"])
    min_tokens = int(limits["min_served_tokens_compared"])
    in_vocab = all(0 <= t < vocab for q in seqs for t in q[2])
    correct = (widest <= limit and compared >= min_tokens and in_vocab
               and not failed)
    return correct, {
        "max_logit_gap": {"value": widest, "limit": limit},
        "served_tokens_compared": {"value": compared, "limit": min_tokens},
        "failed_requests": {"value": failed, "limit": 0},
    }


def stats_dict(engine) -> dict:
    return dataclasses.asdict(engine.manager.stats)


def build(model: types.ModuleType, cfg: dict, seed: int, n_adapters: int):
    """The engine with the model module's weights and ``n_adapters``
    adapters registered; returns (engine, shape, params, adapters on
    device)."""
    from repro.serving import ServingEngine

    s = model.Shape.from_config(cfg)
    engine = ServingEngine(model.program_config(cfg), engine_config(model, cfg, s),
                           key=jax.random.PRNGKey(0))
    key = weights.seed_key(seed)
    kp, ka = jax.random.split(key)
    weights.check_layout(jax.eval_shape(model.make_params, kp, s),
                         engine.params)
    engine.params = None
    gc.collect()
    params = model.make_params(kp, s)
    engine.params = params
    adapters = model.make_adapters(ka, s, n_adapters)
    host = jax.device_get(adapters)
    for i in range(n_adapters):
        name = f"lora-{i}"
        aw = engine.adapters.register(name, jax.random.PRNGKey(i))
        for t in aw.weights:
            a, b = host[t]
            aw.weights[t] = (np.ascontiguousarray(a[i]), np.ascontiguousarray(b[i]))
        engine.register_adapter(name)
    jax.block_until_ready(params)
    return engine, s, params, adapters


def warm_shapes(engine, buckets: tuple[int, ...], block: int) -> None:
    """Compile each mixed-step bucket and the decode step once, through the
    API: one request per bucket whose single prefill chunk fills it."""
    from repro.serving import Request

    rng = np.random.default_rng(0)
    vocab = engine.model_cfg.vocab_size
    for i, b in enumerate(buckets):
        r = Request(f"warm-{b}", "lora-0",
                    tuple(int(x) for x in rng.integers(0, vocab, b)), 3)
        engine.submit(r)
        while r.phase.name != "FINISHED":
            engine.step()


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             root: pathlib.Path = spec.ROOT, allow_cpu: bool = False,
             t_start: Optional[float] = None, cfg_override: Optional[dict] = None,
             mix_override: Optional[dict] = None,
             compile_cache: bool = True, control: bool = False,
             keep_trace: Optional[str] = None) -> dict:
    """One run of one cell; returns the result line (a dict).

    ``control=True`` also puts the low-precision control in the program's
    place on the same sample and judges it the same way
    (``diag["control"]``); the benchmark's own runs never do."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = spec.load_benchmark(root)
    cell = spec.find_cell(bench, cell_name)
    devices = jax.devices() if allow_cpu else require_accelerator(cell["chips"])
    dev = devices[0]
    if compile_cache:
        enable_compile_cache(root)
    counter = CompileCounter()
    cfg = cfg_override or spec.find_config(bench, cell["config"], root)
    mix = mix_override or spec.load_mix(cell["traffic"], root / "bench")
    model = spec.model_module(cfg, root / "bench")
    n_adapters = int(mix["adapters_registered"])
    engine, shape, params, adapters = build(model, cfg, seed, n_adapters)
    t_built = time.perf_counter()
    warm_shapes(engine, engine.prefill.buckets, cfg["serving"]["engine"]["block_size"])
    t_warm = time.perf_counter()
    warm_s = float(mix["warmup_s"])
    t0 = engine.now()
    # arrivals run on past the planned close, which a slow profiler start
    # can push back
    traffic = loadgen.make_traffic(mix, shape.vocab, seed, warm_s + seconds + 10.0, t0)
    h = Harness(engine, traffic, cfg["serving"]["engine"]["block_size"])
    w0 = t0 + warm_s
    trace_dir = root / ".bench_out" / f"trace-{cell_name}"
    if trace:
        # the profiler starts a second before the window so its start-up
        # cost lands in set-up; the window itself is the annotated span
        h.run_until(w0 - 1.0)
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    h.run_until(w0)
    # the window opens now: starting the profiler can take longer than the
    # second allowed for it, and the window keeps its full length
    w0 = engine.now()
    setup_s = time.perf_counter() - t_start
    cc0, stats0 = engine.compile_counts(), stats_dict(engine)
    comp0 = counter.read()
    # the device-side metrics read the dispatches and the trace of the
    # window's first TRACE_S seconds (the whole window where it is shorter);
    # a trace of the whole window would outgrow the run's time limit
    span = jax.profiler.TraceAnnotation(tracered.WINDOW)
    span.__enter__()
    h.record_calls = True
    h.run_until(w0 + min(seconds, TRACE_S))
    h.record_calls = False
    span.__exit__(None, None, None)
    wt, stats_t = engine.now(), stats_dict(engine)
    if trace:
        jax.profiler.stop_trace()
    h.run_until(w0 + seconds)
    w1 = engine.now()
    comp1 = counter.read()
    cc1, stats1 = engine.compile_counts(), stats_dict(engine)
    if cc1 != cc0:
        log(f"compiled inside the window: program entry points {cc0} -> {cc1}")
    in_window = {k: (v[0] - comp0[3].get(k, (0, 0.0))[0],
                     v[1] - comp0[3].get(k, (0, 0.0))[1])
                 for k, v in comp1[3].items()}
    in_window = {k: v for k, v in in_window.items() if v[0] > 0}
    log(f"compile requests inside the window: {comp1[0] - comp0[0]} "
        f"({comp1[1] - comp0[1]} served from the persistent cache), "
        f"{comp1[2] - comp0[2]:.3f} s; by program: "
        f"{sorted(in_window.items(), key=lambda kv: -kv[1][1])[:12]}")
    mem = dev.memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    # ---- correctness: a sample of what the window served, then free
    sample = pick_sample(h, w0, w1, seed)
    kinds: dict[str, int] = {}
    kinds_by_key = {k: h.kinds_of(k) for k in sample}
    seqs = []
    for k in sample:
        for kd in kinds_by_key[k]:
            kinds[kd] = kinds.get(kd, 0) + 1
        r = h.requests[k]
        seqs.append((int(r.adapter_id.split("-")[1]), tuple(r.prompt),
                     tuple(r.output_tokens), int(r.shared_prefix_len)))
    # a traced run's per-layer metrics cover the traced span only: stopping
    # the profiler stalls the loop for seconds after it
    state = RunState(cell=cell, cfg=cfg, mix=mix, model=model, shape=shape,
                     device_kind=dev.device_kind, traced=trace, obs=h.obs,
                     w0=w0, w1=wt if trace else w1, stats0=stats0,
                     stats1=stats_t if trace else stats1,
                     calls=h.calls, compile_s=comp1[2] - comp0[2])
    attempted = len(window.due_in(h.obs.values(), w0, w1))
    failed = [k for k in h.failed if w0 <= h.obs[k].due < w1]
    all_kinds: dict[str, int] = {}
    for k, o in h.obs.items():
        if o.finished is not None and w0 <= o.finished <= w1:
            for kd in h.kinds_of(k):
                all_kinds[kd] = all_kinds.get(kd, 0) + 1
    log(f"window: {attempted} requests due, "
        f"{sum(1 for o in h.obs.values() if o.finished and w0 <= o.finished <= w1)} "
        f"finished in it; kinds finished {all_kinds}; sampled {kinds}")
    h_obs, calls = h.obs, h.calls
    del h, engine, traffic
    gc.collect()
    c = model.Consts.from_config(cfg)
    pad = int(cfg["serving"]["engine"]["max_seq_len"])
    t_ref = time.perf_counter()
    gaps = compare(model, params, adapters, c, seqs, pad)
    log(f"reference over {len(seqs)} requests: {time.perf_counter() - t_ref:.3f} s")
    diag = {"backlog_at_open": backlog(h_obs, w0), "backlog_at_close": backlog(h_obs, w1),
            "finished_in_window": sum(1 for o in h_obs.values()
                                      if o.finished is not None and w0 <= o.finished <= w1),
            "sampled_kinds": kinds, "finished_kinds": all_kinds,
            "compiles_in_window": comp1[0] - comp0[0],
            "cache_hits_in_window": comp1[1] - comp0[1],
            "compile_s_in_window": comp1[2] - comp0[2],
            "dispatches_in_window": len(calls)}
    diag["per_request"] = [[k, sorted(kinds_by_key[k]), len(g), float(g.max())]
                           for k, g in zip(sample, gaps)]
    correct, checks = judge(gaps, seqs, len(failed), cfg["limits"], shape.vocab)
    if control:
        cseqs = control_seqs(model, params, adapters, c, seqs, pad,
                             cfg["limits"]["control_precision"])
        cg = compare(model, params, adapters, c, cseqs, pad)
        for row, g in zip(diag["per_request"], cg):
            row.append(float(g.max()))
        ccorrect, cchecks = judge(cg, cseqs, len(failed), cfg["limits"], shape.vocab)
        diag["control"] = {"correct": ccorrect, "checks": cchecks}
    # ---- metrics
    metrics = {}
    section = "per_layer" if trace else "end_to_end"
    if trace:
        xp = tracered.find_xplane(str(trace_dir))
        host, devs = tracered.load(xp)
        state.trace = tracered.reduce(host, devs)
        if keep_trace:
            shutil.copy(xp, keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        for m in spec.metrics_for(bench, cell_name, "per_layer"):
            v = spec.metric_reader(m["name"], root / "bench")(state)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in spec.metrics_for(bench, cell_name, "end_to_end"):
            if m["name"] == "setup_s":
                v = setup_s
            else:
                v = END_TO_END[m["name"]](state)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": len(failed), "metrics": metrics, "device": device,
              "diag": diag}
    if trace:
        ts = state.trace
        device["busy_s"] = ts.busy_s
        device["window_s"] = ts.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in ts.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in ts.gaps_by_label()[:10]]}
    log(f"setup: built {t_built - t_start:.3f} s, step shapes warmed "
        f"{t_warm - t_built:.3f} s, warm-up traffic until the window "
        f"{setup_s - (t_warm - t_start):.3f} s; setup_s {setup_s:.3f}")
    for name, chk in checks.items():
        log(f"check {name}: {chk['value']} (limit {chk['limit']})")
    result["checks"] = checks
    return result


def backlog(obs: dict, t: float) -> int:
    """Requests due by ``t`` and not admitted by then."""
    return sum(1 for o in obs.values()
               if o.due <= t and (o.admitted is None or o.admitted > t))


def _ms(v: Optional[float]) -> Optional[float]:
    return None if v is None else v * 1e3


END_TO_END = {
    "ttft_p50_ms": lambda s: _ms(window.percentile(
        window.ttfts(s.obs.values(), s.w0, s.w1), 0.50)),
    "ttft_p95_ms": lambda s: _ms(window.percentile(
        window.ttfts(s.obs.values(), s.w0, s.w1), 0.95)),
    "itl_p95_ms": lambda s: _ms(window.percentile(
        window.inter_token_gaps(s.obs.values(), s.w0, s.w1), 0.95)),
    "tokens_per_s": lambda s: (window.tokens_in_window(s.obs.values(), s.w0, s.w1)
                               / s.seconds),
}
