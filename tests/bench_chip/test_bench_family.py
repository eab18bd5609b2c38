"""Families: what the harness knows about one kind of model, in a file of
its own. The dense family reproduces the numbers the harness gave before
it had families (weights, reference gaps, work counts); a family and a
configuration that names it are added as new files only and served
through the harness; a file that names no family, a missing one, or keys
its family does not model, is refused when its cell is resolved."""

from __future__ import annotations

import json

import jax
import numpy as np
import pytest

import benchtiny
import harness

DENSE = benchtiny.DENSE
SEED = 5_000_000_017        # both 32-bit words of the seed are drawn from

# Read at the commit before families, with the same calls, on the CPU.
SUMS = {
    "['blocks'][0]['core']['bk']": -0.9888639450073242,
    "['blocks'][0]['core']['bq']": 3.5994129180908203,
    "['blocks'][0]['core']['bv']": -2.733016014099121,
    "['blocks'][0]['core']['wk']": 45.17660987377167,
    "['blocks'][0]['core']['wo']": 18.49358494579792,
    "['blocks'][0]['core']['wq']": 16.56696492433548,
    "['blocks'][0]['core']['wv']": 17.76677840948105,
    "['blocks'][0]['ffn']['w1']": 47.49865738302469,
    "['blocks'][0]['ffn']['w2']": -8.437581073492765,
    "['blocks'][0]['ffn']['w3']": -1.7015740489587188,
    "['blocks'][0]['norm1']['scale']": 254.4140625,
    "['blocks'][0]['norm2']['scale']": 252.48046875,
    "['embed']['embed']": -3.1249151828233153,
    "['embed']['head']": -3.1249151828233153,
    "['final_norm']['scale']": 125.31640625,
}
GAPS = {
    "float32": [5.993736743927002, 4.203401565551758, 2.4424374103546143,
                4.206504821777344, 3.573129892349243, 5.220468521118164,
                2.0603976249694824, 2.881202459335327],
    "int4": [2.4352002143859863, 0.7363476753234863, 0.7512447834014893,
             0.41888880729675293, 1.2240800857543945, 0.1347358226776123,
             0.7029941082000732, 0.022617340087890625],
    "int4_tensor": [0.686359167098999, 0.5889813899993896,
                    0.10868024826049805, 1.1833617687225342,
                    1.5826207399368286, 0.10392999649047852,
                    2.070319890975952, 2.263631820678711],
}
# prefill chunks (0, 256) and (256, 300, the prompt's end); decode tokens
# at 299, 1000, 4095; decode attention of tokens at 20, 1000, 2047
OPS = {
    "qwen1.5-0.5b": {"model_ops": (188063154176.0, 4968972288.0),
                     "attn_work": (301793280.0, 302088192.0)},
    "yi-6b": {"model_ops": (840874065920.0, 6625296384.0),
              "attn_work": (402391040.0, 50692096.0)},
}


def _tiny() -> dict:
    cfg = json.loads((benchtiny.BENCH / "configs" / "qwen1.5-0.5b.json")
                     .read_text())
    cfg.update(benchtiny.TINY)
    return cfg


def _seqs():
    return [(np.array([(7 * i + 3) % 4096 for i in range(12)], np.int32),
             np.array([11, 250, 4095, 0, 17], np.int32)),
            (np.array([(13 * i + 1) % 4096 for i in range(20)], np.int32),
             np.array([1, 2, 3], np.int32))]


# ------------------------------------------- the move changed nothing
def test_dense_weights_are_the_ones_before_families():
    w = DENSE.make_weights(_tiny(), SEED)
    got = {jax.tree_util.keystr(p): float(np.asarray(leaf, np.float64).sum())
           for p, leaf in jax.tree_util.tree_flatten_with_path(w)[0]}
    assert got == SUMS


@pytest.mark.parametrize("precision", sorted(GAPS))
def test_dense_gaps_are_the_ones_before_families(precision):
    cfg = _tiny()
    w = DENSE.make_weights(cfg, SEED)
    got = DENSE.served_gaps(cfg, w, _seqs(), 96, precision)
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, np.array(GAPS[precision], np.float32))


@pytest.mark.parametrize("name", sorted(OPS))
def test_dense_work_counts_are_the_ones_before_families(name):
    cfg = json.loads((benchtiny.BENCH / "configs" / f"{name}.json")
                     .read_text())
    assert DENSE.model_ops(cfg, [(0, 256, False), (256, 300, True)],
                           [299, 1000, 4095]) == OPS[name]["model_ops"]
    assert DENSE.decode_attention_work(cfg, [20, 1000, 2047]) \
        == OPS[name]["attn_work"]


def test_dense_controls_are_the_int4_pair():
    assert set(DENSE.CONTROLS) == {"int4", "int4_tensor"}


# ------------------------------------------- a family as new files only
PROBE = '''"""A family that serves as the dense decoder and records which of its
functions the harness called."""
import pathlib

import harness

DENSE = harness.load_family(pathlib.Path(__file__).with_name("dense.py"))
CALLS = []
CONTROLS = DENSE.CONTROLS


def _recorded(name):
    def call(*args, **kw):
        CALLS.append(name)
        return getattr(DENSE, name)(*args, **kw)
    return call


for _name in ("check", "arch_overrides", "make_weights", "served_gaps",
              "model_ops", "decode_attention_work"):
    globals()[_name] = _recorded(_name)
'''


def _add_cell(root, name: str, cfg: dict, family_src: str | None = None):
    """Add config ``name``, a chat cell ``name``_chat on it, and its limits,
    as new files and entries of ``root``; with ``family_src``, the family
    file ``cfg["family"]`` too."""
    bench = root / "benchmarks" / "chip"
    (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    lim = (bench / "limits" / "tiny_chat.json").read_text()
    (bench / "limits" / f"{name}_chat.json").write_text(lim)
    if family_src is not None:
        (bench / "families" / f"{cfg['family']}.py").write_text(family_src)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": name, "source": "test",
                            "file": f"benchmarks/chip/configs/{name}.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": f"{name}_chat", "config": name,
                              "traffic": "tchat", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny_chat" in m.get("workloads", []):
            m["workloads"].append(f"{name}_chat")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return f"{name}_chat"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchtiny.make_root(tmp_path_factory.mktemp("family"))


def test_a_new_family_is_served_from_new_files_only(root):
    bench = root / "benchmarks" / "chip"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    cell = _add_cell(root, "tiny_probe", dict(_tiny(), family="probe"),
                     PROBE)
    assert all(p.read_bytes() == b for p, b in before.items())
    res = benchtiny.run(root, cell=cell, seconds=3.0, trace=True,
                        peak={"bf16_flops_per_s": 197e12,
                              "int8_ops_per_s": 393e12,
                              "hbm_bytes_per_s": 819e9})
    assert res["correct"] is True and res["failed"] == 0
    probe = harness.load_family(harness.family_file("probe", bench))
    assert {"check", "arch_overrides", "make_weights", "served_gaps",
            "model_ops"} <= set(probe.CALLS)
    # step_mfu reads the family's model_ops
    assert res["metrics"]["step_mfu.chat"]["value"] > 0


def test_head_dim_apart_from_the_width_is_served_correct(root):
    """hidden 128 over 4 heads of 64: wider heads than d / H = 32."""
    cfg = dict(_tiny(), head_dim=64)
    cell = _add_cell(root, "tiny_hd64", cfg)
    c = harness.resolve(root, cell, root / "benchmarks" / "chip")
    assert harness._arch_config(c).resolved_head_dim == 64
    assert DENSE.shapes(cfg)["core"]["wq"] == (128, 256)
    res = benchtiny.run(root, cell=cell)
    assert res["correct"] is True and res["failed"] == 0


# ---------------------------------------------------- files it refuses
def _without_family(cfg):
    return {k: v for k, v in cfg.items() if k != "family"}


@pytest.mark.parametrize("change, match", [
    (_without_family, "names no family"),
    (lambda cfg: dict(cfg, family="nowhere"), "nowhere.py"),
    (lambda cfg: dict(cfg, num_experts=16), "num_experts"),
    (lambda cfg: dict(cfg, sliding_window=4096), "sliding_window"),
    (lambda cfg: {k: v for k, v in cfg.items() if k != "rope_theta"},
     "rope_theta"),
], ids=["no_family", "missing_family_file", "dense_with_experts",
        "dense_with_sliding_window", "dense_without_rope_theta"])
def test_a_file_its_family_cannot_serve_is_refused(root, change, match):
    name = f"bad_{len(list((root / 'benchmarks/chip/configs').iterdir()))}"
    cell = _add_cell(root, name, change(_tiny()))
    with pytest.raises(harness.CellError, match=match):
        harness.resolve(root, cell, root / "benchmarks" / "chip")


def test_a_program_config_that_differs_from_the_file_is_refused(root):
    """A family whose overrides the program's config cannot take, here a
    head size the registry entry computes otherwise, fails at set-up."""
    cell = harness.resolve(root, "tiny_chat", root / "benchmarks" / "chip")
    over = DENSE.arch_overrides(cell.model)
    cell.family = type("F", (), {"arch_overrides": staticmethod(
        lambda m: dict(over, resolved_head_dim=48))})
    with pytest.raises(harness.CellError, match="resolved_head_dim"):
        harness._arch_config(cell)
