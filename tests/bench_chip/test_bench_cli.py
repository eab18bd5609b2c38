"""``bench.py`` refuses to run where the cell cannot be measured: no TPU,
an unknown device, or a directory that holds only the benchmark."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
import types

import pytest

import benchtiny

import bench  # noqa: E402  (benchtiny put the harness on sys.path)

PEAKS = {"devices": {"TPU v5 lite": {}}}


def _fake_jax(platform, kind, n=1):
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    return types.SimpleNamespace(devices=lambda: [dev] * n)


def test_check_device_refuses_cpu_unknown_kind_and_too_few_chips():
    with pytest.raises(bench.NoDevice, match="no TPU"):
        bench.check_device(_fake_jax("cpu", "cpu"), 1, PEAKS)
    with pytest.raises(bench.NoDevice, match="not in peaks"):
        bench.check_device(_fake_jax("tpu", "TPU v9 imaginary"), 1, PEAKS)
    with pytest.raises(bench.NoDevice, match="needs 4 chips"):
        bench.check_device(_fake_jax("tpu", "TPU v5 lite"), 4, PEAKS)
    assert bench.check_device(_fake_jax("tpu", "TPU v5 lite"), 1, PEAKS) == {}


def test_peaks_table_has_the_v5e():
    p = bench.harness.load_peaks()["devices"]["TPU v5 lite"]
    assert p["bf16_flops_per_s"] == 197e12 and p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/bench.py", "--workload",
         "qwen05_fast_chat", "--seed", "1", "--seconds", "1", "--trace", "0",
         *args], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_exits_nonzero_without_a_tpu():
    r = _run(benchtiny.REPO)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert r.stdout.strip() == ""


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(benchtiny.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(benchtiny.BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
