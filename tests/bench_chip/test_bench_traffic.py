"""The traffic generator: a seed fixes the requests, every seed offers
the same multiset of sizes and gaps, and each traffic file resolves."""

from __future__ import annotations

import json

import numpy as np
import pytest

import benchtiny  # noqa: F401  (puts the harness on sys.path)
import traffic

FILES = sorted((benchtiny.BENCH / "traffic").glob("*.json"))


def _load(path):
    return json.loads(path.read_text())


@pytest.mark.parametrize("path", FILES, ids=[p.stem for p in FILES])
def test_same_seed_same_requests_other_seed_others(path):
    tr = _load(path)
    a = traffic.generate(tr, 2 ** 31 + 17, 10.0, 1000)
    b = traffic.generate(tr, 2 ** 31 + 17, 10.0, 1000)
    c = traffic.generate(tr, 5, 10.0, 1000)
    key = lambda rs: [(r.due_s, r.max_new_tokens, r.prompt.tobytes())  # noqa: E731
                      for r in rs]
    assert key(a) == key(b)
    assert key(a) != key(c)
    # the same work in another order
    assert sorted(r.prompt.size for r in a) == sorted(r.prompt.size for r in c)
    assert sorted(r.max_new_tokens for r in a) \
        == sorted(r.max_new_tokens for r in c)


@pytest.mark.parametrize("path", FILES, ids=[p.stem for p in FILES])
def test_lengths_within_the_file_and_the_engine(path):
    tr = _load(path)
    reqs = traffic.generate(tr, 3, 30.0, 1000)
    p, o, s = tr["prompt"], tr["output"], tr["serving"]
    head = p.get("shared_prefix", {}).get("tokens", 0)
    sizes = np.array([r.prompt.size for r in reqs]) - head
    assert sizes.min() >= p["min"] and sizes.max() <= p["max"]
    assert (sizes % p.get("multiple", 1) == 0).all()
    outs = np.array([r.max_new_tokens for r in reqs])
    assert outs.min() >= o["min"] and outs.max() <= o["max"]
    assert head + p["max"] + o["max"] <= s["max_len"]
    assert s["n_blocks"] * s["block_size"] >= s["max_len"]


def test_open_loop_offers_its_rate_inside_the_window():
    tr = {"arrivals": {"kind": "poisson", "rate_per_s": 7.0},
          "prompt": {"dist": "uniform", "min": 8, "max": 64},
          "output": {"dist": "uniform", "min": 1, "max": 4}}
    reqs = traffic.generate(tr, 1, 20.0, 50)
    due = [r.due_s for r in reqs]
    assert len(reqs) == 140
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 20.0
    gaps = np.diff(due)
    # exponential gaps: the mean gap is the rate's, the spread is wide
    assert abs(gaps.mean() - 1 / 7.0) < 0.01
    assert gaps.std() > 0.5 * gaps.mean()


def test_backlog_is_all_due_at_once():
    tr = {"arrivals": {"kind": "backlog", "requests": 9},
          "prompt": {"dist": "uniform", "min": 8, "max": 64},
          "output": {"dist": "uniform", "min": 1, "max": 4}}
    reqs = traffic.generate(tr, 1, 20.0, 50)
    assert len(reqs) == 9 and {r.due_s for r in reqs} == {0.0}


def test_warm_up_shapes_cover_every_chunk():
    tr = _load(benchtiny.BENCH / "traffic" / "chat.json")
    chunk = tr["serving"]["prefill_chunk"]
    shapes = set(traffic.shapes(tr, 45.0, chunk))
    for r in traffic.generate(tr, 9, 45.0, 1000):
        n = r.prompt.size
        want = ({chunk} if n >= chunk else set()) | (
            {n % chunk} if n % chunk else set())
        assert want <= shapes


def test_preroll_offers_the_rate_before_the_window():
    tr = {"arrivals": {"kind": "poisson", "rate_per_s": 4.0,
                       "preroll_s": 30.0},
          "prompt": {"dist": "uniform", "min": 8, "max": 64},
          "output": {"dist": "uniform", "min": 1, "max": 4}}
    reqs = traffic.generate(tr, 2, 20.0, 50)
    due = np.array([r.due_s for r in reqs])
    assert len(reqs) == 200 and due[0] == -30.0 and due[-1] < 20.0
    # the rate on both sides of the window's opening, and the same work
    # inside the window for every seed
    assert (due < 0).sum() == 120 and (due >= 0).sum() == 80
    other = traffic.generate(tr, 3, 20.0, 50)
    window = lambda rs: (  # noqa: E731
        sorted(r.prompt.size for r in rs if r.due_s >= 0),
        sorted(r.max_new_tokens for r in rs if r.due_s >= 0))
    assert window(reqs) == window(other)
    assert [r.due_s for r in reqs] != [r.due_s for r in other]


def test_onoff_arrivals_keep_the_mean_rate_in_bursts():
    tr = {"arrivals": {"kind": "onoff", "rate_per_s": 3.0, "on_s": 2.0,
                       "off_s": 6.0},
          "prompt": {"dist": "uniform", "min": 8, "max": 64},
          "output": {"dist": "uniform", "min": 1, "max": 4}}
    reqs = traffic.generate(tr, 4, 40.0, 50)
    due = np.array([r.due_s for r in reqs])
    assert len(reqs) == 120 and due[-1] < 40.0
    assert (np.mod(due, 8.0) < 2.0).all()       # only while on
    again = traffic.generate(tr, 4, 40.0, 50)
    assert [r.due_s for r in again] == due.tolist()


def test_shared_prefix_groups_and_their_warm_up_shapes():
    tr = {"arrivals": {"kind": "poisson", "rate_per_s": 5.0},
          "prompt": {"dist": "uniform", "min": 10, "max": 300,
                     "shared_prefix": {"tokens": 1000, "groups": 3}},
          "output": {"dist": "uniform", "min": 1, "max": 4}}
    reqs = traffic.generate(tr, 6, 20.0, 5000)
    heads = {r.prompt[:1000].tobytes() for r in reqs}
    assert len(heads) == 3
    assert all(r.prompt.size >= 1010 for r in reqs)
    chunk, block = 256, 16
    shapes = set(traffic.shapes(tr, 20.0, chunk, block))
    for r in reqs:
        n = r.prompt.size
        # prefilled from the start (prefix not cached) or from the first
        # block after the cached prefix
        for lo in (0, min(1000 // block, (n - 1) // block) * block):
            full, rem = divmod(n - lo, chunk)
            assert ({chunk} if full else set()) | ({rem} if rem else set()) \
                <= shapes

