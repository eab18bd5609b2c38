"""The reduction from trace to metrics, and the operation and byte counts
behind the rooflines and the MFU, checked by hand."""

from __future__ import annotations

import types

import pytest

import benchtiny
import roofline
import trace_reduce as tr

DENSE = benchtiny.DENSE

V5E = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
       "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
KERNEL = ("%centered_int8_matmul.79 = s32[32,2816]{1,0:T(8,128)S(1)} "
          "custom-call(s8[32,1024]{1,0:T(8,128)(4,1)S(1)} %pad.141, "
          "s8[1024,2816]{1,0} %fusion.14, s32[1,2816]{1,0} %c)")


def _synthetic():
    """A 100 us window: a while loop (10-60 us) holding a kernel call
    (20-30 us) and a copy (35-45 us), then a copy at 80-90 us. The host
    is in a decode_step from 5 to 62 us and in bench.wait from 62 on."""
    ops = [("%while.1 = (s32[]) while(...)", 10_000, 50_000),
           (KERNEL, 20_000, 10_000),
           ("%copy.2 = bf16[8,512]{1,0} copy(bf16[8,512] %a)", 35_000,
            10_000),
           ("%copy.3 = bf16[8,512]{1,0} copy(bf16[8,512] %b)", 80_000,
            10_000)]
    modules = [("jit__lambda(42)", 10_000, 50_000),
               ("jit_convert_element_type(7)", 80_000, 10_000)]
    host = [("bench.window", 0, 100_000), ("bench.step", 1_000, 61_000),
            ("decode_step", 5_000, 57_000), ("bench.wait", 62_000, 38_000)]
    return tr.Trace(ops=ops, modules=modules, host=host,
                    window=(0, 100_000))


def test_busy_idle_and_attribution_by_hand():
    t = _synthetic()
    # busy: 10-60 and 80-90 us
    assert t.busy_s == pytest.approx(60e-6)
    assert t.window_s == pytest.approx(100e-6)
    assert tr.idle_gaps(t.ops, t.window) == [(0, 10_000), (60_000, 80_000),
                                             (90_000, 100_000)]
    b = t.breakdown()
    # gap 0-10 us: midpoint 5 us lies in bench.step only (decode_step
    # starts at 5 us, so it covers it too and is the innermost)
    gaps = dict((n, s) for n, s in b["idle_gaps"])
    assert gaps["bench.wait"] == pytest.approx(30e-6)   # 60-80 and 90-100
    assert gaps["decode_step"] == pytest.approx(10e-6)
    ops = dict((n, s) for n, s in b["device_ops"])
    # the loop's own time excludes its two nested ops: 50 - 10 - 10 us
    assert ops["while (s32[])"] == pytest.approx(30e-6)
    assert ops["copy bf16[8,512]"] == pytest.approx(20e-6)
    assert ops["centered_int8_matmul s32[32,2816]"] == pytest.approx(10e-6)


def test_decode_module_found_from_its_host_span():
    t = _synthetic()
    assert tr.module_of_span(t, "decode_step") == "jit__lambda(42)"
    assert t.step_ms("decode_step") == pytest.approx(0.05)
    assert t.step_ms("prefill_chunk") is None


def test_int8_roofline_by_hand():
    t = _synthetic()
    assert tr.int8_matmul_calls(t) == [(32, 1024, 2816, 10_000)]
    ops, nbytes = roofline.int8_matmul(32, 1024, 2816)
    assert ops == 2 * 32 * 1024 * 2816
    assert nbytes == 32 * 1024 + 1024 * 2816 + 4 * 2816 + 4 * 32 * 2816
    # memory bound: 3,288,064 B at 819 GB/s is 4.015 us; ops take 0.47 us
    t_min, bound = roofline.roofline_s(ops, nbytes, V5E)
    assert bound == "memory" and t_min == pytest.approx(4.01473e-6, rel=1e-5)
    assert tr.int8_matmul_roofline_pct(t, V5E) == pytest.approx(
        100 * t_min / 10e-6)


def test_model_ops_by_hand():
    cfg = {"hidden_size": 1024, "intermediate_size": 2816,
           "num_hidden_layers": 24, "num_attention_heads": 16,
           "num_key_value_heads": 16, "vocab_size": 151936}
    per_layer = 4 * 1024 * 1024 + 3 * 1024 * 2816
    assert DENSE.proj_ops_per_token(cfg) == 2 * per_layer * 24
    # one decode token at position 99 attends 100 keys in each layer
    i8, bf = DENSE.model_ops(cfg, [], [99])
    assert i8 == 2 * per_layer * 24 + 2 * 1024 * 151936
    assert bf == 4 * 16 * 64 * 100 * 24
    # a prompt of 3 tokens in one chunk: 1 + 2 + 3 keys, the head once
    i8, bf = DENSE.model_ops(cfg, [(0, 3, True)], [])
    assert i8 == 3 * 2 * per_layer * 24 + 2 * 1024 * 151936
    assert bf == 4 * 16 * 64 * 6 * 24


def test_step_mfu_counts_the_tokens_of_the_traced_window():
    cfg = {"hidden_size": 1024, "intermediate_size": 2816,
           "num_hidden_layers": 24, "num_attention_heads": 16,
           "num_key_value_heads": 16, "vocab_size": 151936}
    trace = _synthetic()
    trace.host_span = (10.0, 11.0)
    run = types.SimpleNamespace(
        model=cfg, family=DENSE, trace=trace, prompt_len={7: 100},
        prefill_chunks=[(10.5, 7, 0, 100), (9.0, 7, 0, 100)],
        token_times={7: [10.6, 10.7, 11.5]})
    i8, bf = DENSE.model_ops(cfg, [(0, 100, True)], [100])
    want = 100 * (i8 / 393e12 + bf / 197e12) / 1.0
    assert tr.step_mfu_pct(run, V5E) == pytest.approx(want)


def test_trace_round_trips_through_json():
    t = _synthetic()
    t.host_span = (1.0, 2.0)
    back = tr.Trace.from_json(t.to_json())
    assert back == t


def test_op_kind_names():
    assert tr.op_kind("%fusion.118 = f32[8,512,16]{2,1,0:T(8,128)} "
                      "fusion(x)") == "fusion f32[8,512,16]"
    assert benchtiny  # the harness is on sys.path


# ------------------------------------------------- a trace from the chip
# 130 ms of a `--trace 1` run of yi6b_fast_batch on a TPU v5 lite (8
# layers at d_model 4096, 64 slots): two prefill chunks of 256 tokens, one
# batched decode step and the scheduler's eager ops. Op names are cut to
# their first 120 characters and times start at the window's start.
CHIP = benchtiny.REPO / "tests" / "bench_chip" / "data" \
    / "trace_yi6b_batch.json.gz"


@pytest.fixture(scope="module")
def chip_trace():
    import gzip
    with gzip.open(CHIP, "rt") as f:
        return tr.Trace.from_json(f.read())


def test_chip_trace_busy_time(chip_trace):
    t = chip_trace
    assert (len(t.ops), len(t.modules)) == (5946, 16)
    assert t.window_s == pytest.approx(0.130)
    # the same union by brute force, at 1 us resolution
    busy = bytearray(130_000)
    for _, s, d in t.ops:
        for us in range(int(s // 1000), int(-(-(s + d) // 1000))):
            busy[us] = 1
    assert t.busy_s == pytest.approx(sum(busy) * 1e-6, abs=2e-5)
    assert t.busy_s == pytest.approx(0.117199, abs=1e-6)


def test_chip_trace_steps(chip_trace):
    # one decode program run (49.45 ms) and two prefill chunk runs (31.08 ms)
    assert chip_trace.step_ms("decode_step") == pytest.approx(49.4516, abs=1e-3)
    assert chip_trace.step_ms("prefill_chunk") == pytest.approx(31.0812,
                                                               abs=1e-3)
    assert tr.module_of_span(chip_trace, "decode_step") \
        != tr.module_of_span(chip_trace, "prefill_chunk")


def test_chip_trace_int8_kernel_calls(chip_trace):
    import collections
    calls = tr.int8_matmul_calls(chip_trace)
    shapes = collections.Counter((m, k, n) for m, k, n, _ in calls)
    # decode: 64 slots through 8 layers (q, k, v, o, w1, w3, w2) and the
    # head; prefill: 256-row chunks, and the head on one row (padded to 8)
    assert shapes[(64, 4096, 4096)] == 16          # wq, wo
    assert shapes[(64, 4096, 512)] == 16           # wk, wv
    assert shapes[(64, 4096, 11008)] == 16         # w1, w3
    assert shapes[(64, 11264, 4096)] == 8          # w2 (K padded to 512s)
    assert shapes[(64, 4096, 64000)] == 1          # head
    assert shapes[(8, 4096, 64000)] == 3
    # one call by hand: 64 x 4096 x 11008 moves 48,212,992 B, 58.87 us at
    # 819 GB/s against 14.7 us of int8 work at 393 TOP/s: memory bound
    t_min, bound = roofline.roofline_s(*roofline.int8_matmul(64, 4096, 11008),
                                       V5E)
    assert bound == "memory" and t_min == pytest.approx(48_212_992 / 819e9)
    assert tr.int8_matmul_roofline_pct(chip_trace, V5E) == pytest.approx(
        46.4913, abs=1e-3)
    assert tr.int8_matmul_roofline_pct(chip_trace, V5E) < 100


def test_chip_trace_breakdown(chip_trace):
    b = chip_trace.breakdown()
    assert len(b["device_ops"]) == 10
    top, secs = b["device_ops"][0]
    # the whole KV pool (11265 blocks) sliced out of its layer stack
    assert top == "dynamic-slice_bitcast_fusion bf16[11265,16,4,128]"
    assert secs == pytest.approx(0.0281452, abs=1e-6)
    idle = dict(b["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(0.13 - chip_trace.busy_s)
    assert set(idle) == {"np.asarray(jax.Array)", "AllocateBufferAwait"}
