"""From a JAX profiler trace to the numbers the per-layer metrics report.

A trace is read once (``load``) into plain tuples, ``(name, start_ns,
duration_ns)``, on the profiler's one clock:

- ``ops``: the ``XLA Ops`` line of the first TPU device plane. The ops of
  a loop body nest inside their ``while`` op.
- ``modules``: its ``XLA Modules`` line, one event per program run; a
  module's name carries the fingerprint of the compiled program.
- ``host``: the host threads' spans: the harness's ``bench.*``
  annotations and the program's ``StepTraceAnnotation``s
  (``decode_step``, ``prefill_chunk``) among them.
- ``window``: the harness's ``bench.window`` span, which brackets the
  traced part of the measured window.

Busy time is the union of the op intervals inside the window; idle time
is the rest. Each idle gap is put down to the innermost host span that
covers its middle.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import json
import pathlib
import re

import roofline

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
HOST_THREADS = ("python", "main")
INT8_KERNEL = "%centered_int8_matmul"
_INT8_SHAPES = re.compile(
    r"= s32\[(\d+),(\d+)\]\S* custom-call\(s8\[(\d+),(\d+)\]")
NAME_CHARS = 240    # an op's HLO text is kept to its head: name, type,
                    # and the first operands (the kernel's shapes)
_OP_HEAD = re.compile(r"%(\S+?)(?:\.\d+)? = (\S+?)(?:\{|\s|$)")


@dataclasses.dataclass
class Trace:
    ops: list
    modules: list
    host: list
    window: tuple
    host_span: tuple | None = None   # the window on the harness's clock (s)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _in_window(self, events):
        w0, w1 = self.window
        return [e for e in events if e[1] >= w0 and e[1] + e[2] <= w1]

    @property
    def busy_s(self) -> float:
        return sum(b - a for a, b in busy_intervals(self.ops, self.window)) \
            * 1e-9

    def step_ms(self, span: str) -> float | None:
        """Mean device time of the program run under host span ``span``."""
        fp = module_of_span(self, span)
        if fp is None:
            return None
        runs = [e for e in self._in_window(self.modules) if e[0] == fp]
        return sum(e[2] for e in runs) / len(runs) * 1e-6 if runs else None

    def breakdown(self, top: int = 10) -> dict:
        self_t = collections.Counter()
        for name, dur in self_times(self._in_window(self.ops)):
            self_t[op_kind(name)] += dur
        gaps = collections.Counter()
        idle = idle_gaps(self.ops, self.window)
        names = host_spans_at(self.host, [(a + b) / 2 for a, b in idle])
        for (a, b), name in zip(idle, names):
            gaps[name] += b - a
        return {"device_ops": [[n, t * 1e-9] for n, t in self_t.most_common(top)],
                "idle_gaps": [[n, t * 1e-9] for n, t in gaps.most_common(top)]}

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls(ops=[tuple(e) for e in d["ops"]],
                   modules=[tuple(e) for e in d["modules"]],
                   host=[tuple(e) for e in d["host"]],
                   window=tuple(d["window"]),
                   host_span=d.get("host_span") and tuple(d["host_span"]))


def load(trace_dir: pathlib.Path) -> Trace:
    """The newest profile under ``trace_dir``."""
    from jax.profiler import ProfileData
    path = max(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
               key=lambda p: p.stat().st_mtime)
    return from_profile(ProfileData.from_file(str(path)))


def from_profile(pd) -> Trace:
    ops, modules, host = [], [], []
    device_seen = False
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX) and not device_seen:
            device_seen = True
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is not None:
                    dest.extend((e.name[:NAME_CHARS], e.start_ns,
                                 e.duration_ns) for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                if line.name.startswith(HOST_THREADS):
                    host.extend((e.name, e.start_ns, e.duration_ns)
                                for e in line.events if e.duration_ns > 0)
    spans = [e for e in host if e[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w = max(spans, key=lambda e: e[2])
    return Trace(ops=sorted(ops, key=lambda e: e[1]),
                 modules=sorted(modules, key=lambda e: e[1]),
                 host=sorted(host, key=lambda e: e[1]),
                 window=(w[1], w[1] + w[2]))


# ------------------------------------------------------------ intervals
def busy_intervals(events, window) -> list[tuple[float, float]]:
    """Union of the events' intervals, clipped to ``window``."""
    w0, w1 = window
    out: list[list[float]] = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        a, b = max(s, w0), min(s + d, w1)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def idle_gaps(events, window) -> list[tuple[float, float]]:
    gaps, t = [], window[0]
    for a, b in busy_intervals(events, window):
        if a > t:
            gaps.append((t, a))
        t = b
    if window[1] > t:
        gaps.append((t, window[1]))
    return gaps


def self_times(events):
    """(name, time not covered by a nested op) for ops sorted by start."""
    out, stack = [], []      # stack: [name, end, child_time]
    for name, s, d in events:
        while stack and stack[-1][1] <= s:
            n, _, child, dur = stack.pop()
            out.append((n, dur - child))
        if stack:
            stack[-1][2] += d
        stack.append([name, s + d, 0.0, d])
    out.extend((n, dur - child) for n, _, child, dur in stack)
    return out


def op_kind(name: str) -> str:
    """``%copy.22 = bf16[8,512]{...} copy(...)`` -> ``copy bf16[8,512]``."""
    m = _OP_HEAD.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:64]


def host_spans_at(host, times) -> list[str]:
    """For each time (ascending), the innermost host span covering it,
    leaving out the window's own span."""
    out, active, i = [], [], 0
    events = [e for e in host if e[0] != WINDOW_SPAN]
    for t in times:
        while i < len(events) and events[i][1] <= t:
            active.append(events[i])
            i += 1
        active = [e for e in active if e[1] + e[2] >= t]
        out.append(min(active, key=lambda e: e[2])[0] if active
                   else "(no host span)")
    return out


def module_of_span(trace: Trace, span: str) -> str | None:
    """The program that most often ran first after host span ``span``
    began: the one that span dispatched."""
    votes = collections.Counter()
    mods = trace.modules
    starts = [m[1] for m in mods]
    for name, s, _ in trace.host:
        if name != span:
            continue
        i = bisect.bisect_left(starts, s)
        while i < len(mods) and not mods[i][0].startswith("jit__lambda"):
            i += 1
        if i < len(mods):
            votes[mods[i][0]] += 1
    return votes.most_common(1)[0][0] if votes else None


# ------------------------------------------------------------- metrics
def int8_matmul_calls(trace: Trace):
    """(m, k, n, device_ns) of every int8 matmul kernel call in the
    window, with the shapes as the kernel received them."""
    out = []
    for name, _, d in trace._in_window(trace.ops):
        if name.startswith(INT8_KERNEL):
            m = _INT8_SHAPES.search(name)
            if m is None:
                raise ValueError(f"unparsed kernel call: {name[:200]}")
            mm, n, _, k = (int(g) for g in m.groups())
            out.append((mm, k, n, d))
    return out


def int8_matmul_roofline_pct(trace: Trace, peak: dict) -> float | None:
    calls = int8_matmul_calls(trace)
    if not calls:
        return None
    bound = sum(roofline.roofline_s(*roofline.int8_matmul(m, k, n), peak)[0]
                for m, k, n, _ in calls)
    return 100.0 * bound / (sum(c[3] for c in calls) * 1e-9)


def step_mfu_pct(run, peak: dict) -> float | None:
    """Model operations of the tokens made in the traced window (the
    family's ``model_ops``), each class over its own peak, as a share of
    the window."""
    tr = run.trace
    if tr is None or tr.host_span is None:
        return None
    a, b = tr.host_span
    prefill = [(lo, hi, hi == run.prompt_len[u])
               for t, u, lo, hi in run.prefill_chunks if a <= t < b]
    decode = [run.prompt_len[u] + k - 1
              for u, times in run.token_times.items()
              for k, t in enumerate(times) if k > 0 and a <= t < b]
    i8, bf = run.family.model_ops(run.model, prefill, decode)
    need = i8 / peak["int8_ops_per_s"] + bf / peak["bf16_flops_per_s"]
    return 100.0 * need / (b - a)
