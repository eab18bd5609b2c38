"""Telemetry subsystem (``repro.obs``): registry/exposition/tracing unit
behavior plus the serve-stack contracts — greedy outputs bit-identical
with telemetry on vs off, live counters matching both ``ServeStats`` and
the jit-collected ``SpeculationStats`` totals, and the pJ/token gauge
agreeing with ``repro.core.energy``."""

import dataclasses
import itertools
import json
import pathlib
import sys

import jax
import numpy as np
import pytest

from repro import configs, obs
from repro.core import energy as en
from repro.models import layers as L
from repro.models import pim
from repro.models import transformer as T
from repro.obs.metrics import MetricsRegistry
from repro.obs.serve import NULL_TELEMETRY, STEP_BUCKETS, ServeTelemetry
from repro.obs.tracing import Tracer
from repro.serve import ContinuousServeEngine, PagedServeEngine, Request
from repro.serve.scheduler import EngineStats, ServeStats

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:  # `benchmarks` package lives at the repo root
    sys.path.insert(0, str(ROOT))


_CACHE: dict = {}


def setup(arch: str = "yi-6b"):
    if arch not in _CACHE:
        cfg = configs.get(arch).reduced()
        params, _ = T.init_params(cfg, jax.random.key(0))
        _CACHE[arch] = (cfg, params)
    return _CACHE[arch]


def mixed_requests(cfg, n=4, seed=0):
    rng = np.random.default_rng(seed)
    plens = [3, 7, 5, 9][:n]
    steps = [6, 3, 9, 4][:n]
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        plens[i]).astype(np.int32),
                    max_new_tokens=steps[i])
            for i in range(n)]


# ------------------------------------------------------------- metrics
def test_histogram_bucket_math():
    """Cumulative ``le`` semantics: a value lands in every bucket whose
    upper bound is >= it (inclusive), plus +Inf."""
    r = MetricsRegistry()
    h = r.histogram("h_seconds", "test", buckets=(1.0, 2.0, 5.0))
    for v in (0.5, 1.0, 3.0, 10.0):
        h.observe(v)
    s = h.get()
    assert s["counts"] == [2, 2, 3, 4]      # le=1, le=2, le=5, +Inf
    assert s["count"] == 4
    assert s["sum"] == pytest.approx(14.5)
    # bucket upper bounds are inclusive: 1.0 counted under le=1
    assert s["counts"][0] == 2


def test_counter_gauge_labels_and_guards():
    r = MetricsRegistry()
    c = r.counter("req_total", "requests", ("engine",))
    c.inc(engine="paged")
    c.inc(2, engine="continuous")
    assert c.get(engine="paged") == 1
    assert c.get(engine="continuous") == 2
    assert c.get(engine="other") == 0.0     # untouched series reads 0
    with pytest.raises(ValueError):
        c.inc(-1, engine="paged")           # counters only go up
    with pytest.raises(ValueError):
        c.inc(engine="paged", extra="x")    # undeclared label name
    g = r.gauge("blocks", "pool")
    g.set(7)
    g.inc(-3)
    assert g.get() == 4
    # idempotent re-declaration returns the same metric ...
    assert r.counter("req_total", "requests", ("engine",)) is c
    # ... but schema drift is refused
    with pytest.raises(ValueError):
        r.gauge("req_total", "requests", ("engine",))
    with pytest.raises(ValueError):
        r.counter("req_total", "requests", ("engine", "reason"))
    with pytest.raises(ValueError):
        r.histogram("h", buckets=(1.0, 1.0))


def test_disabled_registry_is_noop():
    r = MetricsRegistry(enabled=False)
    c = r.counter("x_total", "x", ("engine",))
    c.inc(5, engine="paged")
    r.histogram("h_seconds").observe(0.1)
    assert c.get(engine="paged") == 0.0
    assert r.snapshot() == {}
    assert obs.to_prometheus(r) == "\n"


def test_prometheus_exposition_golden():
    """Byte-exact text exposition: HELP/TYPE headers, label escaping,
    cumulative ``le`` buckets, ``_sum``/``_count``."""
    r = MetricsRegistry()
    r.counter("req_total", "requests served", ("engine",)).inc(
        3, engine="paged")
    r.gauge("pool_frac", "pool occupancy").set(0.25)
    h = r.histogram("lat_seconds", "latency", ("engine",),
                    buckets=(0.5, 1.0))
    h.observe(0.2, engine="paged")
    h.observe(2.0, engine="paged")
    assert obs.to_prometheus(r) == (
        "# HELP lat_seconds latency\n"
        "# TYPE lat_seconds histogram\n"
        'lat_seconds_bucket{engine="paged",le="0.5"} 1\n'
        'lat_seconds_bucket{engine="paged",le="1"} 1\n'
        'lat_seconds_bucket{engine="paged",le="+Inf"} 2\n'
        'lat_seconds_sum{engine="paged"} 2.2\n'
        'lat_seconds_count{engine="paged"} 2\n'
        "# HELP pool_frac pool occupancy\n"
        "# TYPE pool_frac gauge\n"
        "pool_frac 0.25\n"
        "# HELP req_total requests served\n"
        "# TYPE req_total counter\n"
        'req_total{engine="paged"} 3\n')


def test_snapshot_round_trips_json():
    r = MetricsRegistry()
    r.counter("c_total", "c", ("k",)).inc(1, k='a"b\n')
    r.histogram("h_seconds", "h").observe(0.01)
    snap = json.loads(json.dumps(r.snapshot()))
    assert snap["c_total"]["type"] == "counter"
    assert snap["c_total"]["series"][0]["labels"] == {"k": 'a"b\n'}
    assert snap["h_seconds"]["buckets"][0] == obs.DEFAULT_BUCKETS[0]
    # escaping happens only at the exposition face
    assert '\\"b\\n' in obs.to_prometheus(r)


# ------------------------------------------------------------- tracing
def test_tracer_chrome_trace_valid():
    """Deterministic clock; the written document is valid JSON in Chrome
    Trace Event format (the keys Perfetto requires per phase)."""
    t = [0.0]

    def clock():
        t[0] += 10.0
        return t[0]

    tr = Tracer(clock_us=clock)
    tr.name_track(0, "engine")
    tr.name_track(3, "request 2")
    with tr.span("decode_step", n_live=2):
        tr.instant("first_token", tid=3, uid=2)
    tr.complete("queue_wait", 5.0, 12.5, tid=3, uid=2)
    doc = json.loads(json.dumps(tr.chrome_trace()))
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert [m["args"]["name"] for m in metas] == ["engine", "request 2"]
    for e in evs:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] in ("X", "i"):
            assert isinstance(e["ts"], float)
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
    span = next(e for e in evs if e["name"] == "decode_step")
    assert (span["ts"], span["dur"]) == (10.0, 20.0)   # clock ticks 10us
    assert span["args"] == {"n_live": 2}
    inst = next(e for e in evs if e["name"] == "first_token")
    assert inst["ph"] == "i" and inst["tid"] == 3


def test_tracer_disabled_records_nothing():
    tr = Tracer(enabled=False)
    tr.name_track(0, "engine")
    with tr.span("x"):
        tr.instant("y")
    assert tr.events() == []
    assert tr.chrome_trace()["traceEvents"] == []


# ------------------------------------------------------- serve binding
def test_stats_snapshot_parity_across_engines():
    """ONE stats schema: the paged engine shares the ServeStats dataclass
    (EngineStats is an alias, not a fork) and snapshot() covers every
    declared counter plus the derived utilization."""
    assert EngineStats is ServeStats
    cfg, params = setup()
    cont = ContinuousServeEngine(cfg, params, n_slots=2, max_len=32)
    paged = PagedServeEngine(cfg, params, n_slots=2, max_len=32,
                             block_size=4)
    assert type(cont.stats) is type(paged.stats) is ServeStats
    field_names = {f.name for f in dataclasses.fields(ServeStats)}
    for eng in (cont, paged):
        snap = eng.stats.snapshot()
        assert set(snap) == field_names | {"decode_utilization"}
    # record_stats mirrors the full snapshot as gauges
    tel = ServeTelemetry(engine="paged")
    tel.record_stats(paged.stats)
    snap = obs.snapshot(tel.registry)
    for k in field_names | {"decode_utilization"}:
        assert f"repro_serve_stats_{k}" in snap


@pytest.mark.parametrize("engine_cls", [ContinuousServeEngine,
                                        PagedServeEngine])
def test_greedy_bit_identical_with_telemetry(engine_cls):
    """The acceptance contract: threading a live ServeTelemetry (metrics
    + tracing on) through an engine changes no output token."""
    cfg, params = setup()
    kw = dict(n_slots=2, max_len=32, prefill_chunk=4)
    if engine_cls is PagedServeEngine:
        kw["block_size"] = 4
    base = engine_cls(cfg, params, **kw).run(mixed_requests(cfg))
    tel = ServeTelemetry(engine="test", tracing=True)
    eng = engine_cls(cfg, params, telemetry=tel, **kw)
    outs = eng.run(mixed_requests(cfg))
    assert [o.uid for o in outs] == [o.uid for o in base]
    for a, b in zip(outs, base):
        np.testing.assert_array_equal(a.tokens, b.tokens)

    # live counters agree with the engine's own ServeStats
    r = tel.registry
    lab = {"engine": "test"}
    st = eng.stats
    assert r.counter("repro_serve_decode_steps_total", "",
                     ("engine",)).get(**lab) == st.decode_steps
    assert r.counter("repro_serve_prefill_chunks_total", "",
                     ("engine",)).get(**lab) == st.prefill_chunks
    assert r.counter("repro_serve_tokens_generated_total", "",
                     ("engine",)).get(**lab) == sum(
        len(o.tokens) for o in outs)
    done = r.counter("repro_serve_requests_completed_total", "",
                     ("engine", "reason"))
    assert sum(v for _, v in done.series()) == len(outs) == st.completed
    # every request observed one TTFT and one e2e latency
    for name in ("repro_serve_ttft_seconds", "repro_serve_e2e_seconds",
                 "repro_serve_queue_wait_seconds"):
        assert r.histogram(name, "", ("engine",)).get(
            **lab)["count"] == len(outs)
    assert r.histogram("repro_serve_tpot_seconds", "", ("engine",),
                       buckets=STEP_BUCKETS).get(**lab)["count"] == sum(
        len(o.tokens) - 1 for o in outs)

    # the span log is a loadable Chrome trace with per-request lanes
    doc = json.loads(json.dumps(tel.tracer.chrome_trace()))
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"admission", "decode_step", "prefill_chunk", "queue_wait",
            "request", "first_token", "submit"} <= names
    req_events = [e for e in doc["traceEvents"]
                  if e["name"] == "request"]
    assert sorted(e["args"]["uid"] for e in req_events) == [
        o.uid for o in outs]
    for e in req_events:        # request lane convention: tid = uid + 1
        assert e["tid"] == e["args"]["uid"] + 1
    # exposition of the full serve registry parses as one text block
    assert obs.to_prometheus(r).startswith("# HELP")


def test_paged_pool_metrics_under_pressure():
    """A tight block pool drives the eviction/wait/pool hooks; counters
    mirror ServeStats exactly and outputs still match the no-telemetry
    run (eviction-by-recompute replays identical tokens)."""
    cfg, params = setup()
    # 8 blocks is the floor (max_len/block_size); three 17-token requests
    # need 5 blocks each, so admission queues and decode growth evicts
    kw = dict(n_slots=3, max_len=32, prefill_chunk=4, block_size=4,
              n_blocks=8)
    rng = np.random.default_rng(3)

    def reqs():
        return [Request(uid=i, prompt=p, max_new_tokens=8)
                for i, p in enumerate(rng.integers(
                    0, cfg.vocab_size, (3, 9)).astype(np.int32))]

    trace = reqs()
    base = PagedServeEngine(cfg, params, **kw).run(trace)
    tel = ServeTelemetry(engine="paged")
    eng = PagedServeEngine(cfg, params, telemetry=tel, **kw)
    outs = eng.run(trace)
    for a, b in zip(outs, base):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    st, r, lab = eng.stats, tel.registry, {"engine": "paged"}
    assert st.admission_waits + st.evictions > 0   # pressure happened
    assert r.counter("repro_serve_admission_waits_total", "",
                     ("engine",)).get(**lab) == st.admission_waits
    assert r.counter("repro_serve_evictions_total", "",
                     ("engine",)).get(**lab) == st.evictions
    assert r.gauge("repro_serve_peak_blocks_in_use", "",
                   ("engine",)).get(**lab) == st.peak_blocks_in_use
    assert r.gauge("repro_serve_blocks_in_use", "",
                   ("engine",)).get(**lab) == 0    # drained pool


PHASES = ("admission", "prefill_chunk", "first_token", "grow_blocks",
          "decode_inputs", "decode_step")
DECODE_PHASES = ("fetch", "sample", "commit")


@pytest.mark.parametrize("tracing", [False, True], ids=["profiler", "chrome"])
@pytest.mark.parametrize("engine_cls", [PagedServeEngine,
                                        ContinuousServeEngine])
def test_step_phase_spans_reach_the_profiler(monkeypatch, engine_cls,
                                             tracing):
    """One step with a live decode and a prefill that finishes: each
    phase reaches ``jax.profiler.TraceAnnotation`` as ``serve.<phase>``
    with no args, in order, inside ``serve.step`` and not overlapping
    another; ``fetch``/``sample``/``commit`` sit inside
    ``serve.decode_step``; the dispatch ``StepTraceAnnotation``s keep
    their bare names inside their phase; the decode-step histogram counts
    the span. NULL_TELEMETRY enters no annotation at all. The contiguous
    engine grows no blocks."""
    cfg, params = setup()
    phases = tuple(p for p in PHASES if engine_cls is PagedServeEngine
                   or p != "grow_blocks")
    kw = {"block_size": 4} if engine_cls is PagedServeEngine else {}
    tick = itertools.count()
    spans: list = []

    class Annotation:                 # [name, enter tick, exit tick]
        def __init__(self, name, **args):
            assert not args, args     # args would suffix the name
            self.span = [name, None, None]

        def __enter__(self):
            self.span[1] = next(tick)
            spans.append(self.span)

        def __exit__(self, *exc):
            self.span[2] = next(tick)

    class Step(Annotation):
        def __init__(self, name, step_num):
            super().__init__(name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Step)
    rng = np.random.default_rng(0)

    def two_steps(tel):
        eng = engine_cls(cfg, params, n_slots=2, max_len=32,
                         prefill_chunk=4, telemetry=tel, **kw)
        eng.submit(Request(uid=0, max_new_tokens=6, prompt=rng.integers(
            0, cfg.vocab_size, 3).astype(np.int32)))
        eng.step()                    # uid 0 prefilled and decoding
        eng.submit(Request(uid=1, max_new_tokens=6, prompt=rng.integers(
            0, cfg.vocab_size, 4).astype(np.int32)))
        spans.clear()
        eng.step()                    # uid 1's one chunk, both decode
        return eng

    two_steps(NULL_TELEMETRY)
    assert spans == []

    tel = ServeTelemetry(engine="t", tracing=tracing)
    eng = two_steps(tel)
    by = {name: (a, b) for name, a, b in spans}
    assert len(by) == len(spans)      # each span entered once
    assert set(by) == {f"serve.{p}" for p in ("step",) + phases
                       + DECODE_PHASES} | {"decode_step", "prefill_chunk"}
    step = by["serve.step"]
    assert spans[0][0] == "serve.step"
    assert all(step[0] < a and b < step[1] for _, a, b in spans[1:])

    def tiled(outer, names):
        ivs = [by[f"serve.{n}"] for n in names]
        assert all(outer[0] < a and b < outer[1] for a, b in ivs)
        assert all(x[1] < y[0] for x, y in zip(ivs, ivs[1:]))

    tiled(step, phases)
    tiled(by["serve.decode_step"], DECODE_PHASES)
    for d in ("decode_step", "prefill_chunk"):
        outer, inner = by[f"serve.{d}"], by[d]
        assert outer[0] < inner[0] and inner[1] < outer[1]
    assert by["decode_step"][1] < by["serve.fetch"][0]
    assert tel.registry.histogram(
        "repro_serve_decode_step_seconds", "", ("engine",),
        buckets=STEP_BUCKETS).get(engine="t")["count"] \
        == eng.stats.decode_steps == 2
    engine_lane = {e["name"] for e in tel.tracer.events()
                   if e["ph"] == "X" and e["tid"] == 0}
    assert engine_lane == ({"step", *phases, *DECODE_PHASES} if tracing
                           else set())


# ----------------------------------------------------------- pim depth
def test_record_pim_totals_derived_gauges():
    """Derived per-token gauges match the §2.5 component energy model
    applied to the accumulated counters (two folds accumulate)."""
    r = MetricsRegistry()
    tot = {"adc_converts": 100, "no_spec_converts": 400,
           "spec_failures": 5, "spec_attempts": 100,
           "recovery_saturations": 2, "cycles": 64, "macs": 4096}
    obs.record_pim_totals(r, tot, 4, adc_bits=8, engine="e")
    d = obs.record_pim_totals(r, tot, 4, adc_bits=8, engine="e")
    assert d["adc_converts_per_token"] == pytest.approx(200 / 8)
    assert d["spec_failure_rate"] == pytest.approx(10 / 200)
    assert d["saturations_per_token"] == pytest.approx(4 / 8)
    energy = en.pim_work_energy_pj(
        {k: 2 * v for k, v in tot.items()}, 8)
    assert d["pj_per_token"] == pytest.approx(energy["total_pj"] / 8)
    assert d["adc_pj_per_token"] == pytest.approx(energy["e_adc_pj"] / 8)
    assert r.gauge("repro_pim_pj_per_token", "", ("engine",)).get(
        engine="e") == pytest.approx(energy["total_pj"] / 8)
    assert r.counter("repro_pim_adc_converts_total", "",
                     ("engine",)).get(engine="e") == 200


def test_pim_work_energy_pj_components():
    tot = {"adc_converts": 10, "macs": 1000}
    e = en.pim_work_energy_pj(tot, 8)
    assert e["e_adc_pj"] == pytest.approx(
        10 * en.adc_energy_per_convert(8))
    assert e["e_xbar_pj"] == pytest.approx(
        1000 * en.E_CELL_MAX * en.AVG_INPUT_DENSITY
        * en.AVG_WEIGHT_DENSITY["center"])
    assert e["total_pj"] == pytest.approx(
        e["e_adc_pj"] + e["e_digital_pj"] + e["e_xbar_pj"])
    assert en.pim_work_energy_pj({}, 8)["total_pj"] == 0.0


def test_serve_pim_counters_match_collected_stats():
    """End-to-end: the telemetry counters an exact+speculation serve run
    accumulates equal the SpeculationStats totals of a manual
    ``with_pim_stats``-wrapped decode replay of the same request, and
    the pJ/token gauge equals the energy model on those totals."""
    cfg, params = setup()
    cfg = dataclasses.replace(cfg, pim_mode="exact", pim_speculation=True,
                              pim_adc_bits=7)
    prompt = np.asarray(jax.random.randint(
        jax.random.key(1), (4,), 0, cfg.vocab_size), np.int32)
    plans, _ = pim.prepare_pim_params(params, cfg, prompt[None, :])
    steps, max_len = 3, 16

    tel = ServeTelemetry(engine="serve")
    eng = ContinuousServeEngine(cfg, params, n_slots=1, max_len=max_len,
                                plans=plans, telemetry=tel)
    assert tel.wants_pim_stats(cfg) and eng._collect_pim
    outs = eng.run([Request(uid=0, prompt=prompt, max_new_tokens=steps)])
    assert len(outs[0].tokens) == steps

    # manual replay: whole-prompt prefill (bit-identical to the engine's
    # chunked prefill), then the same wrapped decode jit the engine uses
    step_j = jax.jit(L.with_pim_stats(
        lambda p, pl, st, tok: T.decode_step(p, cfg, st, tok, plans=pl)))
    logits, state = jax.jit(
        lambda p, pl, toks: T.prefill(p, cfg, toks, max_len=max_len,
                                      plans=pl))(params, plans,
                                                 prompt[None, :])
    tok = np.argmax(np.asarray(logits[:, -1, :]), -1)[:, None]
    want = dict.fromkeys(L.PIM_STAT_KEYS, 0)
    replay = [int(tok[0, 0])]
    for _ in range(steps - 1):        # first token came from prefill
        logits, state, tot = step_j(params, plans, state,
                                    tok.astype(np.int32))
        tok = np.argmax(np.asarray(logits[:, -1, :]), -1)[:, None]
        replay.append(int(tok[0, 0]))
        for k in want:
            want[k] += int(tot[k])
    np.testing.assert_array_equal(np.asarray(replay, np.int32),
                                  outs[0].tokens)

    r, lab = tel.registry, {"engine": "serve"}
    for k in L.PIM_STAT_KEYS:
        got = r.counter(f"repro_pim_{k}_total", "", ("engine",)).get(**lab)
        assert got == want[k], (k, got, want[k])
    assert want["spec_attempts"] > 0
    n_tok = r.counter("repro_pim_decode_tokens_total", "",
                      ("engine",)).get(**lab)
    assert n_tok == steps - 1
    energy = en.pim_work_energy_pj(want, cfg.pim_adc_bits)
    assert r.gauge("repro_pim_pj_per_token", "", ("engine",)).get(
        **lab) == pytest.approx(energy["total_pj"] / n_tok)
    assert r.gauge("repro_pim_adc_converts_per_token", "",
                   ("engine",)).get(**lab) == pytest.approx(
        want["adc_converts"] / n_tok)


def test_null_telemetry_collects_nothing():
    assert not NULL_TELEMETRY.enabled
    NULL_TELEMETRY.on_submit(0)
    NULL_TELEMETRY.on_token(0)
    NULL_TELEMETRY.on_pim_totals({"adc_converts": 5}, 1)
    with NULL_TELEMETRY.span("x"):
        pass
    assert NULL_TELEMETRY.registry.snapshot() == {}
    assert NULL_TELEMETRY.tracer.events() == []
    cfg, _ = setup()
    exact = dataclasses.replace(cfg, pim_mode="exact")
    assert not NULL_TELEMETRY.wants_pim_stats(exact)


# -------------------------------------------------- benchmark recorder
def test_benchmark_record_compare_rules():
    from benchmarks.run import _TIMING_KEY, _compare
    base = {"a": 1, "ratio": 1.0, "wall_s": 3.0, "nested": {"ok": True},
            "lat_seconds": {"count": 9}, "tok_per_s_decode": 1.0,
            "tags": ["x", "y"]}
    new = {"a": 1, "ratio": 1.05, "wall_s": 99.0, "nested": {"ok": True},
           "lat_seconds": {"count": 0}, "tok_per_s_decode": 9.0,
           "tags": ["x", "y"]}
    problems: list = []
    _compare(base, new, "r", problems, rtol=0.1)
    assert problems == []                 # timings pruned, floats in rtol
    _compare(base, {**new, "ratio": 1.5}, "r", problems, rtol=0.1)
    assert any("ratio" in p for p in problems)
    problems = []
    _compare({"flag": True}, {"flag": 1}, "r", problems, rtol=0.1)
    assert problems                       # bools never coerce to ints
    problems = []
    _compare({"a": 1, "b": 2}, {"a": 1}, "r", problems, rtol=0.1)
    assert any("missing" in p for p in problems)
    assert _TIMING_KEY.search("repro_serve_ttft_seconds")
    assert not _TIMING_KEY.search("budget_tokens")


def test_write_metrics_document(tmp_path):
    r = MetricsRegistry()
    r.counter("c_total", "c").inc(2)
    path = tmp_path / "m.json"
    doc = obs.write_metrics(r, str(path), config={"arch": "x"})
    loaded = json.loads(path.read_text())
    assert loaded == json.loads(json.dumps(doc))
    assert loaded["config"] == {"arch": "x"}
    assert "c_total 2" in loaded["prometheus"]
    assert loaded["metrics"]["c_total"]["series"][0]["value"] == 2
