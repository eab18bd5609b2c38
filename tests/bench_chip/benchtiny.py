"""A tiny cell of the chip benchmark that a CPU test run can hold: the
qwen1.5-0.5b configuration file at d_model 128 and two layers, served
through the same harness, engine and reference as the real cells."""

from __future__ import annotations

import json
import pathlib
import shutil
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

DENSE = harness.load_family(harness.family_file("dense", BENCH))
TINY = dict(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4, vocab_size=4096)
# The widest logit gap at this size (CPU, seeds 1-3): 0.04-0.16 for the
# program, 0.34-0.54 for the int4 control. The limit sits between.
TINY_LIMIT = 0.25
TRAFFIC = {
    "arrivals": {"kind": "poisson", "rate_per_s": 20.0, "preroll_s": 1.0},
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.6, "min": 8,
               "max": 64, "multiple": 8},
    "output": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 4,
               "max": 24},
    "serving": {"pim": "fast", "n_slots": 4, "block_size": 8,
                "prefill_chunk": 32, "max_len": 96, "n_blocks": 48},
}


BACKLOG = dict(TRAFFIC, arrivals={"kind": "backlog", "requests": 12},
               prompt={"dist": "uniform", "min": 16, "max": 48,
                       "multiple": 8},
               output={"dist": "uniform", "min": 8, "max": 24})


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout-like root: the real ``src``, a ``BENCHMARK.json`` with
    two cells, ``tiny_chat`` (open loop) and ``tiny_batch`` (backlog), and
    their files beside the real metric readers, checks and families."""
    bench = tmp / "benchmarks" / "chip"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    (bench / "limits").mkdir()
    shutil.copytree(BENCH / "metrics", bench / "metrics")
    shutil.copytree(BENCH / "checks", bench / "checks")
    shutil.copytree(BENCH / "families", bench / "families",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp / "src").symlink_to(REPO / "src")
    cfg = json.loads((BENCH / "configs" / "qwen1.5-0.5b.json").read_text())
    cfg.update(TINY)
    (bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tchat.json").write_text(json.dumps(TRAFFIC))
    (bench / "traffic" / "tbatch.json").write_text(json.dumps(BACKLOG))
    lim = {"limits": {"max_logit_gap": TINY_LIMIT}, "sample_tokens": 64,
           "sample_rows": 4}
    for cell in ("tiny_chat", "tiny_batch"):
        (bench / "limits" / f"{cell}.json").write_text(json.dumps(lim))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "benchmarks/chip/configs/tiny.json",
                        "reduced": sorted(TINY), "why": "test"}]
    spec["workloads"] = [{"name": f"tiny_{k}", "config": "tiny",
                          "traffic": f"t{k}", "chips": 1, "why": "test"}
                         for k in ("chat", "batch")]
    # each metric keeps to the tiny cell of its real cell's kind
    kind = {"qwen05_fast_chat": "tiny_chat", "yi6b_fast_batch": "tiny_batch"}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [kind[w] for w in m["workloads"]]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run(root: pathlib.Path, seed: int = 5, seconds: float = 2.0,
        trace: bool = False, hook=None, control: bool = False,
        cell: str = "tiny_chat", peak: dict | None = None) -> dict:
    """One run of a tiny cell on the CPU (the look for a chip skipped)."""
    cell = harness.resolve(root, cell, root / "benchmarks" / "chip")
    return harness.run_cell(cell, seed, seconds, trace, peak=peak,
                            t_process=time.perf_counter(), hook=hook,
                            control=control)
