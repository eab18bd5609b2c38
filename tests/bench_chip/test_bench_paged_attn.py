"""``paged_attn_roofline``: the paged decode attention kernel's share of
its roofline, checked by hand on a synthetic trace."""

from __future__ import annotations

import importlib.util
import types

import pytest

import benchtiny
import harness
import trace_reduce as tr

V5E = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
       "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
YI = {"hidden_size": 4096, "num_hidden_layers": 8, "num_attention_heads": 32,
      "num_key_value_heads": 4, "intermediate_size": 11008,
      "vocab_size": 64000}
KERNEL = ("%paged_decode_attention.3 = f32[64,32,128]{2,1,0} "
          "custom-call(s32[16384]{0} %t, s32[64]{0} %l, bf16[64,32,128] %q)")


def _run(kernel_ns):
    """Request 7 (prompt 1000) receives tokens at 9.9, 10.2, 10.4 and
    11.2 s; request 8 (prompt 20) at 10.1 and 10.3 s; the traced span is
    10-11 s. The window holds kernel calls of ``kernel_ns`` each and a
    copy."""
    ops = [("%while.1 = (s32[]) while(...)", 1_000, 90_000)]
    ops += [(KERNEL, 2_000 + 30_000 * i, d) for i, d in enumerate(kernel_ns)]
    ops.append(("%copy.2 = bf16[8,512]{1,0} copy(bf16[8,512] %a)", 95_000,
                1_000))
    trace = tr.Trace(ops=ops, modules=[], host=[("bench.window", 0, 100_000)],
                     window=(0, 100_000), host_span=(10.0, 11.0))
    return types.SimpleNamespace(
        model=YI, family=benchtiny.DENSE, peak=V5E, trace=trace,
        prompt_len={7: 1000, 8: 20},
        token_times={7: [9.9, 10.2, 10.4, 11.2], 8: [10.1, 10.3]})


def _read(name, run):
    return harness.read_metric(name, run, benchtiny.BENCH)


def _reader_module():
    path = harness.metric_file("paged_attn_roofline", benchtiny.BENCH)
    spec = importlib.util.spec_from_file_location("paged_attn_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bytes_of_known_decode_positions():
    m = _reader_module()
    run = _run([1])
    # request 7's tokens 1 and 2 (positions 1000, 1001) and request 8's
    # token 1 (position 20) land in the span; token 0 is the prefill's
    assert sorted(m.decode_positions(run)) == [20, 1000, 1001]
    ops, nbytes = benchtiny.DENSE.decode_attention_work(YI, [20, 1000, 1001])
    keys = 21 + 1001 + 1002
    # per layer: K and V of every key (4 heads x 128), then each token's
    # query and output (32 heads x 128), all bf16
    assert nbytes == 8 * 2 * (2 * 4 * 128 * keys + 2 * 32 * 128 * 3)
    assert ops == 4 * 32 * 128 * keys * 8
    assert ops / nbytes < V5E["bf16_flops_per_s"] / V5E["hbm_bytes_per_s"]


@pytest.mark.parametrize("name", ["paged_attn_roofline.chat",
                                  "paged_attn_roofline.batch"])
def test_kernel_at_its_least_time_reads_100(name):
    _, nbytes = benchtiny.DENSE.decode_attention_work(YI, [20, 1000, 1001])
    least_ns = nbytes / V5E["hbm_bytes_per_s"] * 1e9
    assert harness.metric_file(name).name == "paged_attn_roofline.py"
    share = _read(name, _run([least_ns / 2, least_ns / 2]))
    assert share == pytest.approx(100.0) and share <= 100.0 + 1e-9
    assert _read(name, _run([least_ns, least_ns])) == pytest.approx(50.0)


def test_reads_nothing_without_the_kernel():
    run = _run([])
    assert _read("paged_attn_roofline.chat", run) is None
    run.trace.ops.append((KERNEL, 150_000, 5_000))    # after the window
    assert _read("paged_attn_roofline.chat", run) is None
    assert _read("paged_attn_roofline.batch",
                 types.SimpleNamespace(trace=None, peak=V5E)) is None
