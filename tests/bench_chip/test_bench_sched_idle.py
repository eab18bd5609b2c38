"""``sched_idle_ms``: device idle time per decode step inside the serve
engine's ``serve.step`` spans and outside its jitted dispatches, checked
by hand on a synthetic trace."""

from __future__ import annotations

import types

import pytest

import benchtiny
import harness
import trace_reduce as tr


def _synthetic():
    """A 100 us window. The device is busy at 10-30, 50-60 and 85-95 us,
    so it idles at 0-10, 30-50, 60-85 and 95-100 us. The engine is in
    ``serve.step`` at 5-45 us (decode dispatch 35-40) and at 55-90 us
    (decode dispatch 62-66, prefill dispatch 70-75); the harness waits
    from 90 us on. A third decode dispatch starts after the window."""
    ops = [("%fusion.1 = bf16[8,512]{1,0} fusion(x)", 10_000, 20_000),
           ("%copy.2 = bf16[8,512]{1,0} copy(y)", 50_000, 10_000),
           ("%copy.3 = bf16[8,512]{1,0} copy(z)", 85_000, 10_000)]
    host = [("bench.window", 0, 100_000),
            ("bench.step", 4_000, 42_000), ("serve.step", 5_000, 40_000),
            ("serve.decode_step", 34_000, 10_000),
            ("decode_step", 35_000, 5_000),
            ("bench.step", 54_000, 37_000), ("serve.step", 55_000, 35_000),
            ("serve.decode_step", 61_000, 8_000),
            ("decode_step", 62_000, 4_000),
            ("serve.prefill_chunk", 69_000, 7_000),
            ("prefill_chunk", 70_000, 5_000),
            ("bench.wait", 90_000, 10_000),
            ("decode_step", 120_000, 5_000)]
    return tr.Trace(ops=ops, modules=[], host=host, window=(0, 100_000))


def _read(name, trace):
    return harness.read_metric(name, types.SimpleNamespace(trace=trace),
                               benchtiny.BENCH)


@pytest.mark.parametrize("name", ["sched_idle_ms.chat", "sched_idle_ms.batch"])
def test_sched_idle_by_hand(name):
    t = _synthetic()
    assert tr.idle_gaps(t.ops, t.window) == [(0, 10_000), (30_000, 50_000),
                                             (60_000, 85_000),
                                             (95_000, 100_000)]
    # first step: idle 5-10 and 30-45 us (20 us), less the dispatch at
    # 35-40 us: 15 us. Second: idle 60-85 us (25 us), less the dispatches
    # at 62-66 and 70-75 us: 16 us. Idle outside serve.step (0-5, 45-50,
    # 95-100 us) does not count. 31 us over the window's 2 decode steps.
    assert harness.metric_file(name).name == "sched_idle_ms.py"
    assert _read(name, t) == pytest.approx(0.0155)


def test_sched_idle_reads_nothing_without_device_ops_or_engine_spans():
    t = _synthetic()
    assert _read("sched_idle_ms.batch", None) is None
    no_ops = tr.Trace(ops=[], modules=[], host=t.host, window=t.window)
    assert _read("sched_idle_ms.batch", no_ops) is None
    # a program without the engine's spans, or a window without a decode
    no_spans = [e for e in t.host if not e[0].startswith("serve.")]
    assert _read("sched_idle_ms.batch", tr.Trace(
        ops=t.ops, modules=[], host=no_spans, window=t.window)) is None
    no_decode = [e for e in t.host if e[0] != "decode_step"]
    assert _read("sched_idle_ms.batch", tr.Trace(
        ops=t.ops, modules=[], host=no_decode, window=t.window)) is None

