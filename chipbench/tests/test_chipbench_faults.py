"""A run of a tiny cell on the CPU, with the chip check skipped: sound runs
come out correct, and each fault planted in the timed path, and the fp8
control, come out not correct."""

from __future__ import annotations

import time
from types import SimpleNamespace as NS

import jax.numpy as jnp
import pytest

import chipbench_testcell
from chipbench import harness

SEED = 2**33 + 17


def _run(root, cell="tiny.closed", seconds=1.5, control=False):
    return harness.run_cell(root, cell, SEED, seconds, False,
                            t_process=time.perf_counter(), control=control)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return chipbench_testcell.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    """CPU programs kept in the checkout's compile cache would be carried
    to a machine with a chip, where JAX fails to write beside them."""
    from repro.launch import serve
    monkeypatch.setattr(serve, "use_compile_cache", lambda: None)


@pytest.mark.parametrize("cell", ["tiny.closed", "tiny.open"])
def test_sound_run_is_correct(root, cell):
    out = _run(root, cell)
    res = out["result"]
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "ttft_p50_ms",
                                   "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert out["notes"]["compiles_in_window"] == 0
    assert list(res)[-1] == "checks"


def test_fp8_control_is_not_correct(root):
    """The reference in fp8 in the program's place, through the same
    comparison, comes out not correct, while the program on the same sample
    comes out correct."""
    out = _run(root, control=True)
    r = out["readings"]
    assert out["result"]["correct"] and r["control_correct"] is False
    assert r["logit_gap"] <= chipbench_testcell.LIMIT
    assert r["control_logit_gap"] > chipbench_testcell.LIMIT


def _req(i, n, max_new=None):
    return NS(request_id=f"r{i}", prompt=[1, 2], output_tokens=[5] * n,
              sampling=NS(max_new_tokens=max_new or n))


def test_sample_covers_every_slot():
    """The longest request, then one of each slot, before the caps."""
    done = [_req(i, 10 + (i == 5) * 100) for i in range(40)]
    slot_of = {f"r{i}": i % 8 for i in range(40)}
    limits = {"sample_tokens": 50, "sample_requests": 4}
    for seed in (1, 2**40 + 7):
        sample = harness.pick_sample(done, limits, seed, slot_of)
        assert len(sample) == 8 and len(sample[0][1]) == 110
        assert harness.pick_sample(done, limits, seed, slot_of) == sample
    # without slots, the caps alone
    assert len(harness.pick_sample(done, limits, 1, {})) == 1
    assert len(harness.pick_sample(done, dict(limits, sample_tokens=200),
                                   1, {})) == 4


def test_stalls_of_a_window():
    from chipbench.loop import Tick
    ticks = [Tick(0.5, 0.55)] + [Tick(1 + 0.1 * k, 1.05 + 0.1 * k)
                                 for k in range(9)] + [Tick(2.0, 2.4)]
    s = harness.stalls(ticks, 1.0, 3.0)
    assert s["longest_tick_ms"] == pytest.approx(400)
    assert s["longest_between_ticks_ms"] == pytest.approx(150)
    assert s["ticks_over_3x_median"] == 1
    assert s["seconds_in_ticks_over_3x_median"] == pytest.approx(0.4)
    assert harness.stalls(ticks, 5.0, 6.0) == {}


def _state_unchanged(orig):
    def step(model, params, caches, tokens, index, slots):
        logits, _ = orig(model, params, caches, tokens, index, slots)
        return logits, caches
    return step


def _half_batch(orig):
    def step(model, params, caches, tokens, index, slots):
        logits, caches = orig(model, params, caches, tokens, index, slots)
        keep = logits.shape[0] - logits.shape[0] // 2
        return jnp.concatenate([logits[:keep], logits[:logits.shape[0] - keep]
                                ]), caches
    return step


def _token_altered(orig):
    def sample(logits, key, params):
        return (orig(logits, key, params) + 1) % logits.shape[-1]
    return sample


@pytest.mark.parametrize("fault,target,wrap", [
    ("state returned unchanged", "packed_step", _state_unchanged),
    ("half the batch left out", "packed_step", _half_batch),
    ("token altered where produced", "sample", _token_altered),
])
def test_fault_is_not_correct(root, monkeypatch, fault, target, wrap):
    from repro.serving import engine
    monkeypatch.setattr(engine, target, wrap(getattr(engine, target)))
    res = _run(root)["result"]
    assert not res["correct"], (fault, res["checks"])
