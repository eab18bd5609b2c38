"""Chip benchmark: one cell, one run.

    python3 benchmarks/chip/bench.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine with the chips the cell
asks for. The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration, traffic mix, correctness limit and metrics are files under
this directory, found by name:

- ``configs/<config>.json``: the model, as its public ``config.json``
  states it, and under ``family`` the kind of model it is;
- ``families/<family>.py``: what the harness knows about that kind of
  model: the file's keys mapped onto the program's config, the weights,
  the plain reference and its controls, and the work counts behind the
  rooflines and the MFU (``families/__init__.py`` gives the interface);
- ``traffic/<traffic>.json``: arrivals, lengths and the serving settings
  (``traffic.py`` is the one generator);
- ``limits/<workload>.json``: the numbers ``correct`` compares, each
  with its limit, and the size of the sample; ``checks/<number>.py``
  reads a number from the sampled gaps;
- ``metrics/<metric>.py``: one reader per metric, ``read(run) -> value``
  or ``None`` where the run holds nothing to read. A metric split by
  kind of cell, ``<name>.<kind>``, is read by ``metrics/<name>.py``
  unless ``metrics/<name>.<kind>.py`` exists.

A run makes the weights from ``--seed``, compiles the PIM plans, builds
the program's paged engine, warms up every shape the traffic drives and,
for a backlog, fills every slot, or, for an open loop with a pre-roll,
offers its arrivals for that long (``setup_s`` ends there), then serves
the traffic for ``--seconds`` through ``PagedServeEngine.submit``/``step``. Afterwards it frees the
program's state and runs the plain reference over a sample, drawn from
the seed, of the requests the window finished. With ``--trace 1`` the last
part of the window runs under the JAX profiler and the cell's per-layer
metrics are read from that trace; with ``--trace 0`` its end-to-end
metrics are reported.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``: each number compared with its
limit). A run without a TPU, with fewer chips than the cell asks for, or
on a device missing from ``peaks.json`` exits non-zero and prints no
result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import harness  # noqa: E402


class NoDevice(Exception):
    """The machine lacks what the cell needs; no result is printed."""


def check_device(jax, chips: int, peaks: dict) -> dict:
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {d.platform} devices")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found {len(devs)}")
    if d.device_kind not in peaks["devices"]:
        raise NoDevice(f"device kind {d.device_kind!r} is not in peaks.json")
    return peaks["devices"][d.device_kind]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = harness.resolve(harness.ROOT, args.workload)
        harness.use_compile_cache()
        import jax
        peak = check_device(jax, cell.chips, harness.load_peaks())
        res = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                               peak=peak, t_process=T_PROCESS)
    except (NoDevice, harness.CellError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
