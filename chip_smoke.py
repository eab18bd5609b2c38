"""Chip smoke test: serve qwen1.5-0.5b at full width on TPU.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py               # one chip: phases (a), (b), (c)
    python chip_smoke.py --four-chips  # four chips: disaggregated serving only

One chip, one process, three phases:

(a) device: the first device must be a TPU, ``REPRO_KERNEL_BACKEND`` must
    be unset, and every kernel op must resolve to ``pallas-tpu``. Each
    kernel is then run at the model's widths (the lm_head's d_model x
    vocab included) and must be bit-exact, counters included, against
    ``repro/kernels/ref.py`` (the ``xla`` backend); paged decode
    attention, whose f32 sums run in another order, within one bf16 ulp.
(b) ``fast`` serve: ``repro.launch.serve``'s own steps drive the paged
    engine at full width on the Pallas kernels and again on ``xla``. The
    prefill and first-decode logits must agree within ``LOGIT_RTOL``, and
    the greedy tokens must be identical: the kernels are bit-exact and
    the serving steps compile without XLA's excess precision
    (``repro.serve.engine.jit_step``), so the two runs are bit-identical
    by construction.
(c) ``exact`` serve: the bit-exact datapath against the ``int8``
    reference at the same compiled plan. That contract is bit-exact, so
    the greedy tokens must be identical. Converts per token are printed.

``--four-chips`` runs only the disaggregated path: prefill on two chips
and decode on the other two (``make_disaggregated_meshes``), compared
with the same trace on the one-device paged engine by the logits gate of
(b). Tokens are printed, not gated: sharding may reorder float
reductions, so the two are not bit-identical by construction, and with
random weights two logits often nearly tie.

Digests of tokens and logits are printed so that two runs can be
compared. The phase functions also take another kernel backend and
``reduced=True``: that is how they are rehearsed on the CPU at smoke size
(``interpret`` kernels, ``JAX_PLATFORMS=cpu``) before a chip run.

The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``; any failure exits non-zero without it.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import sys
import time

ARCH = "qwen1.5-0.5b"
# Relative L2 distance allowed between the logits rows of two kernel
# backends (or two device layouts) fed the same tokens. On one device the
# gap is 0 by construction; across device layouts only float rounding
# differs. The fast path's own quantization error against the int8
# reference is ~5% (tests/test_pim_model.py), so 1% still catches a
# kernel that computes different arithmetic.
LOGIT_RTOL = 1e-2
FAST_ARGS = ["--requests", "8", "--prompt-len", "24", "--steps", "16",
             "--slots", "4"]
EXACT_ARGS = ["--requests", "2", "--prompt-len", "8", "--steps", "4",
              "--slots", "2"]


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(a.tobytes())
    return h.hexdigest()[:16]


# ---------------------------------------------------------------- (a)
def phase_kernels(cfg, kernel: str, batch: int = 8) -> None:
    """Each kernel at ``cfg``'s widths, ``kernel`` against kernels/ref.py."""
    import jax
    import numpy as np

    from repro.core import adc, center_offset as co, speculation
    from repro.kernels import ops, ref

    key = jax.random.key(11)
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab_size
    for n in (f, v):
        kx, kw, kc, key = jax.random.split(key, 4)
        x = jax.random.randint(kx, (batch, d), -127, 128).astype("int8")
        w = jax.random.randint(kw, (d, n), -127, 128).astype("int8")
        c = jax.random.randint(kc, (n,), -128, 128)
        got = ops.centered_int8_matmul(x, w, c, backend=kernel)
        want = ref.centered_int8_matmul(x, w, c)
        check(bool((got == want).all()),
              f"centered_int8_matmul {batch}x{d}x{n}: {kernel} != ref")
        print(f"(a) centered_int8_matmul {batch}x{d}x{n}: bit-exact vs ref")

    rng = np.random.default_rng(12)
    enc = co.encode(rng.integers(0, 256, (d, f)), (4, 2, 2))
    x_u8 = jax.random.randint(key, (batch, d), 0, 256)
    lo, hi = adc.RAELLA_ADC.lo, adc.RAELLA_ADC.hi
    common = dict(adc_lo=lo, adc_hi=hi, rows_per_xbar=enc.rows_per_xbar)
    outs = {}
    for be in (kernel, "xla"):
        outs[be] = jax.device_get(ops.fused_crossbar_forward(
            x_u8, enc.planes, enc.shifts, enc.centers,
            input_slicing=(1,) * 8, backend=be, **common))
    (p, s), (pr, sr) = outs[kernel], outs["xla"]
    check(np.array_equal(p, pr) and int(s) == int(sr),
          f"fused_crossbar {d}x{f}: {kernel} != ref")
    print(f"(a) fused_crossbar {batch}x{d}x{f} slicing (4, 2, 2): psum and "
          f"{int(s)} saturations bit-exact vs ref")
    for be in (kernel, "xla"):
        outs[be] = jax.device_get(ops.fused_spec_crossbar_forward(
            x_u8, enc.planes, enc.shifts, enc.centers,
            spec_slicing=speculation.SPEC_SLICING, backend=be, **common))
    (p, fl, rs), (pr, flr, rsr) = outs[kernel], outs["xla"]
    check(np.array_equal(p, pr) and np.array_equal(fl, flr)
          and int(rs) == int(rsr),
          f"fused_spec_crossbar {d}x{f}: {kernel} != ref")
    print(f"(a) fused_spec_crossbar {batch}x{d}x{f} spec slicing "
          f"{speculation.SPEC_SLICING}: psum, spec failures {fl.tolist()} "
          f"and {int(rs)} recovery saturations bit-exact vs ref")

    # paged decode attention: 4 of `batch` slots live, in scrambled blocks
    H, K, hd, bs = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim, 16
    lens = np.array([700, 1, 16, 2048] + [1] * (batch - 4))
    n_blocks, max_blocks = 320, 128
    tables = np.full((batch, max_blocks), n_blocks, np.int32)
    order, used = rng.permutation(n_blocks), 0
    for b, n in enumerate(-(-lens[:4] // bs)):
        tables[b, :n] = order[used:used + n]
        used += n
    kq, kk, kv = jax.random.split(key, 3)
    pool = (n_blocks + 1, bs, K, hd)
    args = (jax.random.normal(kq, (batch, H, hd), "bfloat16"),
            jax.random.normal(kk, pool, "bfloat16"),
            jax.random.normal(kv, pool, "bfloat16"), tables, lens)
    got, want = (np.asarray(ops.paged_decode_attention(
        *args, sentinel=n_blocks, backend=be), np.float32)
        for be in (kernel, "xla"))
    err = np.abs(got[:4] - want[:4]).max()
    # both round an f32 result to bf16 once: one bf16 ulp apart at most
    check(bool(np.allclose(got[:4], want[:4], rtol=2 ** -7, atol=1e-5))
          and not got[4:].any(),
          f"paged_decode_attention: {kernel} vs ref max |diff| {err}")
    print(f"(a) paged_decode_attention {batch} slots x {H} heads, lengths "
          f"{lens[:4].tolist()}: max |diff| vs ref {err:.3g} (within one "
          f"bf16 ulp), all-sentinel slots exact zeros")


# ---------------------------------------------------------- serving
def serve_recorded(args, s, eng=None):
    """Serve ``args``' trace through ``repro.launch.serve.run``, keeping
    each request's first two logits rows (prefill, first decode)."""
    import numpy as np

    from repro.launch import serve

    eng = serve.make_engine(args, s) if eng is None else eng
    rows: dict[int, list] = {}
    sample = eng._sample

    def recording_sample(slot, logits_row, greedy_tok):
        kept = rows.setdefault(slot.req.uid, [])
        if len(kept) < 2:
            kept.append(np.asarray(logits_row, np.float32))
        return sample(slot, logits_row, greedy_tok)

    eng._sample = recording_sample
    outs = serve.run(args, s, eng)
    return {o.uid: o.tokens for o in outs}, rows, eng


def compare_logits(tag: str, got, want, gate_tokens: bool) -> None:
    """Gate on prefill and first-decode logits, and on greedy tokens
    where ``gate_tokens``; print both.

    A first-decode row is compared only where both runs fed it the same
    first token; past a differing token the two runs see different
    inputs."""
    import numpy as np

    (toks, rows), (toks_r, rows_r) = got, want
    check(sorted(toks) == sorted(toks_r), f"{tag}: different requests")
    worst_rel = worst_abs = 0.0
    n_rows = 0
    for uid in sorted(toks):
        n = 1 + int(toks[uid][0] == toks_r[uid][0])
        for a, b in zip(rows[uid][:n], rows_r[uid][:n]):
            check(bool(np.isfinite(a).all() and np.isfinite(b).all()),
                  f"{tag}: non-finite logits for request {uid}")
            worst_rel = max(worst_rel, float(np.linalg.norm(a - b)
                                             / np.linalg.norm(b)))
            worst_abs = max(worst_abs, float(np.abs(a - b).max()))
            n_rows += 1
    same = [uid for uid in toks if np.array_equal(toks[uid], toks_r[uid])]
    first_diff = {uid: int(np.argmax(toks[uid] != toks_r[uid]))
                  for uid in toks if uid not in same}
    print(f"{tag}: greedy tokens identical for {len(same)}/{len(toks)} "
          f"requests; first differing token per request {first_diff} "
          f"({'gated' if gate_tokens else 'printed, not gated'})")
    print(f"{tag}: {n_rows} prefill/first-decode logits rows, max relative "
          f"L2 distance {worst_rel:.3e}, max abs difference {worst_abs:.3e} "
          f"(gate: relative <= {LOGIT_RTOL})")
    check(worst_rel <= LOGIT_RTOL,
          f"{tag}: logits differ by {worst_rel:.3e} > {LOGIT_RTOL}")
    check(not gate_tokens or len(same) == len(toks),
          f"{tag}: greedy tokens differ for {sorted(first_diff)}")


def print_digest(tag: str, toks, rows) -> None:
    uids = sorted(toks)
    print(f"{tag} digest: tokens {digest(toks[u] for u in uids)}, "
          f"logits {digest(r for u in uids for r in rows[u])}")


def serve_argv(pim: str, kernel: str, reduced: bool, sizes) -> list[str]:
    argv = ["--arch", ARCH, "--engine", "paged", "--pim", pim,
            "--kernel-backend", kernel, *sizes]
    return argv + ["--reduced"] if reduced else argv


def phase_fast(kernel: str, reduced: bool = False) -> None:
    """(b) the paged fast path on ``kernel`` against ``xla``."""
    from repro.launch import serve

    args = serve.parse_args(serve_argv("fast", kernel, reduced, FAST_ARGS))
    s = serve.setup(args)
    got = serve_recorded(args, s)[:2]
    print_digest(f"(b) fast/{kernel}", *got)
    args_r = serve.parse_args(serve_argv("fast", "xla", reduced, FAST_ARGS))
    s_r = dataclasses.replace(s, cfg=dataclasses.replace(
        s.cfg, pim_kernel_backend="xla"))
    want = serve_recorded(args_r, s_r)[:2]
    print_digest("(b) fast/xla", *want)
    compare_logits(f"(b) fast {kernel} vs xla", got, want, gate_tokens=True)


def phase_exact(kernel: str, reduced: bool = False) -> None:
    """(c) the exact datapath against the int8 reference, same plans."""
    import numpy as np

    from repro.launch import serve

    args = serve.parse_args(serve_argv("exact", kernel, reduced, EXACT_ARGS))
    s = serve.setup(args)
    toks, rows, eng = serve_recorded(args, s)
    print_digest(f"(c) exact/{kernel}", toks, rows)
    reg = eng.tel.registry
    converts, n_tok = (int(reg.counter(f"repro_pim_{k}_total", "",
                                       ("engine",)).get(engine=eng.tel.engine))
                       for k in ("adc_converts", "decode_tokens"))
    check(converts > 0 and n_tok > 0, "(c) exact: no converts were counted")
    args_i = serve.parse_args(serve_argv("int8", kernel, reduced, EXACT_ARGS))
    s_i = dataclasses.replace(s, cfg=dataclasses.replace(s.cfg,
                                                         pim_mode="int8"))
    toks_i, rows_i, _ = serve_recorded(args_i, s_i)
    print_digest("(c) int8", toks_i, rows_i)
    same = all(np.array_equal(toks[u], toks_i[u]) for u in toks)
    n = sum(len(t) for t in toks.values())
    print(f"(c) exact: {converts / n_tok:.1f} converts/token over {n_tok} "
          f"decode tokens; greedy tokens {'identical' if same else 'DIFFER'}"
          f" to int8 over {len(toks)} requests ({n} tokens)")
    check(sorted(toks) == sorted(toks_i) and same,
          f"(c) exact vs int8: greedy tokens differ\n{toks}\n{toks_i}")


def phase_four_chips(reduced: bool = False) -> None:
    """Disaggregated prefill/decode on 2+2 devices against one device."""
    from repro import obs
    from repro.launch import serve
    from repro.launch.mesh import make_disaggregated_meshes
    from repro.serve import PagedServeEngine

    args = serve.parse_args(serve_argv("fast", "auto", reduced, FAST_ARGS))
    s = serve.setup(args)
    want = serve_recorded(args, s)[:2]
    print_digest("(4) one device", *want)
    prefill_mesh, decode_mesh = make_disaggregated_meshes((1, 1, 2),
                                                          (1, 1, 2))
    eng = PagedServeEngine(
        s.cfg, s.params, n_slots=args.slots, max_len=serve.max_len_for(args),
        prefill_chunk=args.prefill_chunk, block_size=args.block_size,
        plans=s.plans, prefill_mesh=prefill_mesh, decode_mesh=decode_mesh,
        telemetry=obs.ServeTelemetry(engine="paged"))
    got = serve_recorded(args, s, eng)[:2]
    print_digest("(4) disaggregated 2+2", *got)
    compare_logits("(4) disaggregated 2+2 vs one device", got, want,
                   gate_tokens=False)


# --------------------------------------------------------------- main
def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the disaggregated prefill/decode path "
                         "on four chips")
    args = ap.parse_args(argv)
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    try:
        check(not os.environ.get("REPRO_KERNEL_BACKEND"),
              "REPRO_KERNEL_BACKEND is set; the smoke test checks what "
              "'auto' resolves to")
        import jax

        from repro import configs
        from repro.launch import serve

        devs = jax.devices()
        check(devs[0].platform == "tpu",
              f"no TPU: JAX found {devs[0].platform} devices")
        want_n = 4 if args.four_chips else 1
        check(len(devs) >= want_n, f"need {want_n} chips, found {len(devs)}")
        cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        serve.use_compile_cache()
        print("compile cache: "
              + ("JAX_COMPILATION_CACHE_DIR" if cache else
                 ".jax_cache in the checkout"))
        rep = serve.device_report()
        print(f"device: {rep['platform']} {rep['kind']} x{rep['count']}")
        for op, be in rep["kernels"].items():
            print(f"(a) {op}: auto -> {be}")
        check(all(be == "pallas-tpu" for be in rep["kernels"].values()),
              "an op did not resolve to pallas-tpu")
        if args.four_chips:
            phases = [("four chips", phase_four_chips)]
        else:
            cfg = configs.get(ARCH)
            phases = [("(a) kernels", lambda: phase_kernels(cfg,
                                                            "pallas-tpu")),
                      ("(b) fast serve", lambda: phase_fast("pallas-tpu")),
                      ("(c) exact serve", lambda: phase_exact("pallas-tpu"))]
        for name, fn in phases:
            t0 = time.monotonic()
            fn()
            print(f"{name}: ok in {time.monotonic() - t0:.1f}s on "
                  f"{rep['kind']}, compiles included")
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    device = {k: rep[k] for k in ("platform", "kind", "count")}
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
