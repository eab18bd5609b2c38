"""``correct`` on a tiny cell, driven through the whole harness on the CPU
with the look for a chip skipped: a sound run passes; the int4 control
and each fault a served cell can have, planted under the timed path, do
not."""

from __future__ import annotations

import jax.numpy as jnp
import pytest

import benchtiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchtiny.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(scope="module")
def sound(root):
    return benchtiny.run(root, control=True)


def test_sound_run_is_correct(sound):
    assert sound["correct"] is True
    assert sound["attempted"] > 0 and sound["failed"] == 0
    c = sound["checks"]["max_logit_gap"]
    assert c["value"] is not None and c["value"] <= c["limit"]
    assert list(sound)[-1] == "checks"
    assert set(sound["metrics"]) == {"setup_s", "tpot_p95_ms"}
    # set-up ends as the window opens, after the pre-roll
    assert sound["metrics"]["setup_s"]["value"] > 1.0


def test_backlog_cell_runs_from_full_slots(root):
    res = benchtiny.run(root, cell="tiny_batch", seconds=1.0)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "tokens_per_s"}
    assert res["metrics"]["tokens_per_s"]["value"] > 0


def test_traced_run_reports_the_per_layer_metrics_it_can_read(root):
    """On the CPU no device plane exists: the device metrics are left
    out, the host-side ones are read, and busy/window are present."""
    res = benchtiny.run(root, seconds=3.0, trace=True)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"plan_compile_s"}
    assert res["device"]["window_s"] > 0 and "breakdown" in res


def test_int4_control_fails_the_limit(sound):
    """Each control, put in the program's place and judged by the cell's
    own limits, comes out not correct; the program's tokens do not."""
    ctl = sound["control"]
    assert ctl["program"]["correct"] is True
    assert ctl["program"]["checks"] == sound["checks"]
    for name in ("int4", "int4_tensor"):
        assert ctl[name]["correct"] is False
        c = ctl[name]["checks"]["max_logit_gap"]
        assert c["value"] > c["limit"]


def _state_unchanged(eng):
    """The decode step hands back the state it was given."""
    decode = eng._decode

    def broken(params, plans, state, toks, tables):
        logits, _ = decode(params, plans, state, toks, tables)
        return logits, state
    eng._decode = broken


def _half_batch(eng):
    """Half of the batch left out: the first half's rows repeat the
    second half's (slots fill from the first, so that half is busy)."""
    decode = eng._decode

    def broken(*args):
        logits, state = decode(*args)
        keep = logits[logits.shape[0] // 2:]
        return jnp.concatenate([keep, keep])[: logits.shape[0]], state
    eng._decode = broken


def _token_altered(eng):
    """Each request's third token altered where it is picked."""
    sample = eng._sample

    def broken(slot, row, greedy):
        tok = sample(slot, row, greedy)
        return (tok + 1) % row.shape[-1] if len(slot.tokens) == 2 else tok
    eng._sample = broken


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered],
                         ids=["state_unchanged", "half_batch",
                              "token_altered"])
def test_fault_under_the_timed_path_is_not_correct(root, fault):
    res = benchtiny.run(root, hook=fault)
    assert res["correct"] is False
    c = res["checks"]["max_logit_gap"]
    assert c["value"] is None or c["value"] > c["limit"]
