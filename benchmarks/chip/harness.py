"""What one run of a cell does: resolve its files, set up the program,
serve the traffic through the window, read the metrics, check the
served tokens against the plain reference.

Everything that depends on the kind of model (its weights, its plain
reference and controls, its work counts) comes from the family its
configuration names, ``families/<family>.py``; this module calls only
the interface ``families/__init__.py`` describes.

``bench.py`` is the command; this module is what it and the tests drive.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import sys
import time

import stats
import traffic as traffic_gen

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = HERE / ".jax_cache"
TRACE_DIR = HERE / ".traces"
DRAIN_S = 60.0          # how long past the window a due request may take
CALIB_SHAPE = (2, 64)   # calibration tokens for the PIM plan compile
TRACE_SHARE = 0.2       # the traced part: the end of the window, this
TRACE_MAX_S = 10.0      # share of it or this long, whichever is shorter


class CellError(Exception):
    """A workload that does not resolve to its files."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    root: pathlib.Path          # checkout root (holds src/ and BENCHMARK.json)
    bench_dir: pathlib.Path     # where its configs, traffic, limits, metrics are
    model: dict                 # configs/<config>.json
    family: object              # families/<model["family"]>.py, loaded
    traffic: dict               # traffic/<traffic>.json
    limits: dict                # limits/<workload>.json
    end_to_end: list[dict]
    per_layer: list[dict]


def _for_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(root: pathlib.Path, workload: str,
            bench_dir: pathlib.Path = HERE) -> Cell:
    """The files of ``workload`` as ``root/BENCHMARK.json`` names them."""
    try:
        spec = json.loads((root / "BENCHMARK.json").read_text())
    except (OSError, ValueError) as e:
        raise CellError(f"no readable BENCHMARK.json under {root}: {e}")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise CellError(f"unknown workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    files = {"config": bench_dir / "configs" / f"{w['config']}.json",
             "traffic": bench_dir / "traffic" / f"{w['traffic']}.json",
             "limits": bench_dir / "limits" / f"{workload}.json"}
    missing = [str(p) for p in files.values() if not p.is_file()]
    metrics = spec["end_to_end"] + spec["per_layer"]
    missing += [str(metric_file(m["name"], bench_dir)) for m in metrics
                if _for_cell(m, workload)
                and not metric_file(m["name"], bench_dir).is_file()]
    if missing:
        raise CellError(f"{workload}: missing {missing}")
    limits = json.loads(files["limits"].read_text())
    missing = [str(check_file(k, bench_dir)) for k in limits["limits"]
               if not check_file(k, bench_dir).is_file()]
    if missing:
        raise CellError(f"{workload}: missing {missing}")
    model = json.loads(files["config"].read_text())
    if "family" not in model:
        raise CellError(f"{files['config']}: names no family")
    path = family_file(model["family"], bench_dir)
    if not path.is_file():
        raise CellError(f"{workload}: missing {path}")
    family = load_family(path)
    try:
        family.check(model)
    except ValueError as e:
        raise CellError(f"{files['config']}: {e}")
    return Cell(
        name=workload, chips=int(w["chips"]), root=root, bench_dir=bench_dir,
        model=model, family=family,
        traffic=json.loads(files["traffic"].read_text()),
        limits=limits,
        end_to_end=[m for m in spec["end_to_end"] if _for_cell(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _for_cell(m, workload)])


def metric_file(name: str, bench_dir: pathlib.Path = HERE) -> pathlib.Path:
    """``metrics/<name>.py``; for a name split by cell kind, such as
    ``idle_pct.chat``, the reader of the whole quantity,
    ``metrics/idle_pct.py``, unless the kind has one of its own."""
    own = bench_dir / "metrics" / f"{name}.py"
    base = bench_dir / "metrics" / f"{name.split('.')[0]}.py"
    return own if own.is_file() or not base.is_file() else base


def check_file(name: str, bench_dir: pathlib.Path = HERE) -> pathlib.Path:
    return bench_dir / "checks" / f"{name}.py"


def family_file(name: str, bench_dir: pathlib.Path = HERE) -> pathlib.Path:
    return bench_dir / "families" / f"{name}.py"


def _module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reader(path: pathlib.Path):
    return _module(path).read


_FAMILIES: dict = {}


def load_family(path: pathlib.Path):
    """The family module at ``path``, loaded once per process (its jitted
    functions keep their compiled programs across cells)."""
    path = path.resolve()
    if path not in _FAMILIES:
        _FAMILIES[path] = _module(path)
    return _FAMILIES[path]


def read_metric(name: str, run, bench_dir: pathlib.Path = HERE):
    """Load the metric's reader (``metric_file``) and return its
    ``read(run)``."""
    return _reader(metric_file(name, bench_dir))(run)


def load_peaks() -> dict:
    return json.loads((HERE / "peaks.json").read_text())


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    caching every program however fast it compiled."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


# ------------------------------------------------------------- the run
@dataclasses.dataclass
class Run:
    """What a metric reader reads. Times are ``time.perf_counter()``
    seconds; ``t0``/``t_end`` bound the measured window."""
    model: dict
    family: object              # the cell's family module
    peak: dict | None
    setup_s: float
    plan_compile_s: float
    t0: float
    t_end: float
    due: dict                   # uid -> due time (requests due in window)
    admitted: dict              # uid -> first admission time
    token_times: dict           # uid -> commit time of each token
    prompt_len: dict            # uid -> prompt length
    prefill_chunks: list        # (time, uid, lo, hi)
    stats_at_open: dict         # ServeStats.snapshot() as the window opens
    stats_at_close: dict        # and at its close
    trace: object = None        # trace_reduce.Trace of the traced part


class _Compiles:
    """Counts tracing and compiling (cache loads included) events."""

    def __init__(self):
        import jax.monitoring as mon
        self.n = {"trace": 0, "compile": 0}
        mon.register_event_duration_secs_listener(self._on)

    def close(self) -> None:
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.n["trace"] += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.n["compile"] += 1


def _arch_config(cell: Cell):
    """The program's config: the registry entry the file's ``arch`` names,
    with the family's overrides and the traffic's PIM mode. Every
    attribute the family names has to read back as it gave it."""
    from repro import configs
    base = configs.get(cell.model["arch"])
    over = cell.family.arch_overrides(cell.model)
    fields = {f.name for f in dataclasses.fields(base)}
    arch = dataclasses.replace(
        base, **{k: v for k, v in over.items() if k in fields},
        pim_mode=cell.traffic["serving"]["pim"], pim_kernel_backend="auto")
    differ = {k: (v, getattr(arch, k, None)) for k, v in over.items()
              if getattr(arch, k, None) != v}
    if differ:
        raise CellError(f"{cell.model['arch']}: the program's config differs "
                        f"from the file (file, program): {differ}")
    return arch


def _annotate(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax.profiler
    return jax.profiler.TraceAnnotation(name)


def _start_trace(trace_dir: pathlib.Path):
    """Start the profiler (host annotations and device ops, no Python
    call tracing) and open the span that marks the traced window."""
    import jax.profiler
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    ann = jax.profiler.TraceAnnotation("bench.window")
    ann.__enter__()
    return ann


@dataclasses.dataclass
class Session:
    """The program as set up for one cell: weights, plans, a warm engine."""
    params: object
    eng: object
    tel: object
    plan_compile_s: float
    request_cls: type


def build(cell: Cell, seed: int, seconds: float, trace: bool) -> Session:
    """Weights from ``seed``, the PIM plan compile, the paged engine, and
    a warm-up of every prefill and decode shape the traffic drives."""
    src = str(cell.root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax
    import numpy as np
    from repro.models import pim
    from repro.serve import PagedServeEngine, Request
    from repro.serve.scheduler import ServeStats

    import bench_telemetry

    serving, model = cell.traffic["serving"], cell.model
    arch = _arch_config(cell)
    vocab = model["vocab_size"]
    rng = np.random.default_rng([seed, 1])
    params = jax.block_until_ready(cell.family.make_weights(model, seed))
    calib = rng.integers(0, vocab, CALIB_SHAPE, dtype=np.int32)
    t = time.perf_counter()
    compiled = pim.compile_pim_params(params, arch, calib)
    plans = jax.block_until_ready(compiled.plans)
    plan_compile_s = time.perf_counter() - t
    del compiled
    tel = bench_telemetry.BenchTelemetry(annotate=trace)
    eng = PagedServeEngine(
        arch, params, n_slots=serving["n_slots"], max_len=serving["max_len"],
        prefill_chunk=serving["prefill_chunk"],
        block_size=serving["block_size"], n_blocks=serving["n_blocks"],
        plans=plans, telemetry=tel)
    del plans
    shapes = traffic_gen.shapes(cell.traffic, seconds,
                                serving["prefill_chunk"],
                                serving["block_size"])
    t_warm = time.perf_counter()
    for i, n in enumerate(shapes):
        eng.submit(Request(uid=-1 - i, max_new_tokens=2,
                           prompt=rng.integers(0, vocab, n, dtype=np.int32)))
    while eng.has_work:
        eng.step()
    jax.block_until_ready(eng.state)
    eng.stats = ServeStats()
    tel.clear()
    print(f"set-up: plan compile {plan_compile_s:.2f} s, warm-up of "
          f"{len(shapes)} prefill shapes and the decode step "
          f"{time.perf_counter() - t_warm:.2f} s", file=sys.stderr)
    return Session(params=params, eng=eng, tel=tel,
                   plan_compile_s=plan_compile_s, request_cls=Request)


def fill_slots(sess: Session, reqs: list) -> None:
    """Submit a backlog and step until every slot holds a request past
    its prefill: an offline batch job is measured in its steady state,
    and this ramp is part of its set-up."""
    eng, Request = sess.eng, sess.request_cls
    for r in reqs:
        eng.submit(Request(uid=r.uid, prompt=r.prompt,
                           max_new_tokens=r.max_new_tokens))
    while eng.has_work and any(s is None or not s.live for s in eng.slots):
        eng.step()


@dataclasses.dataclass
class Window:
    t0: float
    t_end: float
    due: dict                   # uid -> due time of each request due in it
    late: list                  # submit time minus due time, per request
    finished: dict              # uid -> served tokens, finished in it
    stats_at_open: dict
    stats_at_close: dict
    compiles: dict              # tracing / compiling events in the window
    trace_span: tuple | None    # traced part, on the host clock


def serve(sess: Session, reqs: list, seconds: float, *, backlog: bool,
          preroll_s: float = 0.0, drain: bool = True,
          trace_dir: pathlib.Path | None = None) -> Window:
    """Offer ``reqs`` at their due times through the engine's
    ``submit``/``step``; the window opens ``preroll_s`` seconds after the
    call, and requests due before it (``due_s < 0``) are its pre-roll.
    Past the close no request is submitted; an open loop with ``drain``
    steps on until every request due in the window has its first token
    (at most ``DRAIN_S`` more). With ``trace_dir`` the end of the window
    runs under the profiler; its ``bench.window`` span closes with the
    window, and the profiler is stopped (and its trace written) only once
    the loop is done, so that no stall of the profiler's lands inside a
    request's timing."""
    eng, tel, Request = sess.eng, sess.tel, sess.request_cls
    tr0 = seconds - min(TRACE_MAX_S, seconds * TRACE_SHARE)
    tracing, window_ann, trace_span = False, None, None
    due, late, finished = {}, [], {}
    stats_at_open = stats_at_close = None
    compiles = _Compiles()
    n_open = n_close = None
    i = 0
    t0 = time.perf_counter() + preroll_s
    t_end = t0 + seconds
    while True:
        now = time.perf_counter()
        if stats_at_open is None and now >= t0:
            stats_at_open, n_open = eng.stats.snapshot(), dict(compiles.n)
        if trace_dir and not tracing and now >= t0 + tr0:
            window_ann = _start_trace(trace_dir)
            tracing, t_tr = True, time.perf_counter()
        if window_ann is not None and now >= t_end:
            window_ann.__exit__(None, None, None)
            window_ann, trace_span = None, (t_tr, time.perf_counter())
        with _annotate(tracing, "bench.submit"):
            while i < len(reqs) and t0 + reqs[i].due_s <= now \
                    and reqs[i].due_s < seconds:
                r = reqs[i]
                eng.submit(Request(uid=r.uid, prompt=r.prompt,
                                   max_new_tokens=r.max_new_tokens))
                if r.due_s >= 0:
                    due[r.uid] = t0 + r.due_s
                    late.append(now - due[r.uid])
                i += 1
        if now >= t_end:
            if stats_at_close is None:
                stats_at_close, n_close = eng.stats.snapshot(), \
                    dict(compiles.n)
            waiting = [u for u in due if u not in tel.token_times]
            if backlog or not drain or not waiting \
                    or now >= t_end + DRAIN_S:
                break
        if eng.has_work:
            with _annotate(tracing, "bench.step"):
                for o in eng.step():
                    if now >= t0:
                        finished[o.uid] = o.tokens
        else:
            nxt = t0 + reqs[i].due_s if i < len(reqs) else t_end
            with _annotate(tracing, "bench.wait"):
                time.sleep(max(0.0, min(nxt, t_end) - now))
    if tracing:
        import jax.profiler
        jax.profiler.stop_trace()
    compiles.close()
    return Window(t0=t0, t_end=t_end, due=due, late=late, finished=finished,
                  stats_at_open=stats_at_open, stats_at_close=stats_at_close,
                  compiles={k: n_close[k] - n_open[k] for k in n_close},
                  trace_span=trace_span)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             peak: dict | None, t_process: float, hook=None,
             control: bool = False) -> dict:
    """One run of ``cell``. ``hook(engine)``, where given, is called once
    the engine is warm (the tests break the timed path through it). With
    ``control`` the result also holds, under ``control``, each control of
    the family's ``CONTROLS`` judged on the same sample by the same limits
    as the served tokens: its numbers and its ``correct``; benchmark runs
    never run the controls."""
    sess = build(cell, seed, seconds, trace)
    import jax
    if hook is not None:
        hook(sess.eng)
    reqs = traffic_gen.generate(cell.traffic, seed, seconds,
                                cell.model["vocab_size"])
    backlog = cell.traffic["arrivals"]["kind"] == "backlog"
    trace_dir = (cell.bench_dir / TRACE_DIR.name / f"{cell.name}-{seed}"
                 if trace else None)
    if backlog:
        fill_slots(sess, reqs)
    preroll = traffic_gen.preroll_s(cell.traffic)
    win = serve(sess, [] if backlog else reqs, seconds, backlog=backlog,
                preroll_s=preroll, trace_dir=trace_dir)
    # set-up ends as the window opens: a backlog's slots filled, an open
    # loop's pre-roll offered
    setup_s = win.t0 - t_process
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    tel = sess.tel
    run = Run(model=cell.model, family=cell.family, peak=peak,
              setup_s=setup_s, plan_compile_s=sess.plan_compile_s,
              t0=win.t0, t_end=win.t_end,
              due=win.due if not backlog else {},
              admitted=dict(tel.admitted), token_times=tel.token_times,
              prompt_len={r.uid: r.prompt.size for r in reqs},
              prefill_chunks=list(tel.prefill_chunks),
              stats_at_open=win.stats_at_open,
              stats_at_close=win.stats_at_close)
    if backlog:
        # requests still running at the close are unfinished, not failed
        attempted = [u for u, ta in tel.admitted.items() if ta < win.t_end]
        failed = []
    else:
        attempted = list(win.due)
        failed = [u for u in attempted if u not in tel.token_times]
    params, finished, trace_span = sess.params, win.finished, win.trace_span

    # ---- free the program's state, then the reference
    del sess
    gc.collect()
    prompts = {r.uid: r.prompt for r in reqs}
    checks, ctl = check_served(cell, params, prompts, finished, seed,
                               control)
    if failed:
        checks["requests_without_a_first_token"] = {
            "value": len(failed), "limit": 0}
    correct = verdict(checks)

    metrics, breakdown = {}, None
    if trace and trace_span is not None:
        import trace_reduce
        run.trace = trace_reduce.load(trace_dir)
        run.trace.host_span = trace_span
        breakdown = run.trace.breakdown()
    names = cell.per_layer if trace else cell.end_to_end
    for m in names:
        v = read_metric(m["name"], run, cell.bench_dir)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
    print(f"compilations in the window: {win.compiles['compile']} "
          f"(traces {win.compiles['trace']})")
    if win.late:
        print(f"generator lateness: p50 "
              f"{stats.percentile(win.late, 0.5) * 1e3:.3f} ms, max "
              f"{max(win.late) * 1e3:.3f} ms over {len(win.late)} submits")
    out = {"correct": bool(correct), "attempted": len(attempted),
           "failed": len(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if control:
        out["control"] = ctl
    out["checks"] = checks
    return out


def verdict(checks: dict) -> bool:
    """Correct when every number compared has a value within its limit."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def check_served(cell: Cell, params, prompts: dict, finished: dict,
                 seed: int, control: bool = False) -> tuple[dict, object]:
    """Gaps, over a sample of finished requests drawn from the seed (the
    longest among them), by which a served token's logit lies below the
    plain reference's best; each number the cell's limits file names is
    read from the gaps by its reader, ``checks/<name>.py``, and held to
    its limit (the gaps come from the family's ``served_gaps``). With
    ``control``, each control of the family's ``CONTROLS`` is put in the
    program's place on the same sample and held to the same limits:
    ``{control: {"checks": ..., "correct": ...}}``."""
    import numpy as np

    family, lim = cell.family, cell.limits
    readers = {k: _reader(check_file(k, cell.bench_dir))
               for k in lim["limits"]}
    rng = np.random.default_rng([seed, 2])
    uids = sorted(finished)
    sample = []
    if uids:
        longest = max(uids, key=lambda u: (finished[u].size, -u))
        sample, n_tok = [longest], finished[longest].size
        for u in rng.permutation(uids):
            if n_tok >= lim["sample_tokens"] or len(sample) >= lim["sample_rows"]:
                break
            if int(u) != longest:
                sample.append(int(u))
                n_tok += finished[int(u)].size
    seqs = [(prompts[u], np.asarray(finished[u], np.int32)) for u in sample]

    def judge(precision):
        got = dict.fromkeys(readers)
        if seqs:
            g = family.served_gaps(cell.model, params, seqs,
                                   cell.traffic["serving"]["max_len"],
                                   precision)
            got = {k: f(g) for k, f in readers.items()}
        return {k: {"value": (float(v) if v is not None and math.isfinite(v)
                              else None),
                    "limit": lim["limits"][k]} for k, v in got.items()}

    checks = judge("float32")
    print(f"reference: {len(seqs)} requests, "
          f"{sum(s.size for _, s in seqs)} served tokens compared",
          file=sys.stderr)
    ctl = None
    if control:
        ctl = {"program": {"checks": checks, "correct": verdict(checks)}}
        for p in family.CONTROLS:
            c = judge(p)
            ctl[p] = {"checks": c, "correct": verdict(c)}
    return checks, ctl
