"""Property-based tests for the unified step-pricing roofline.

For ANY batch size and KV depths, ``decode_charge(kv_lens)`` is the closed
form: FLOPs ``2 * params * n``, HBM ``params * bytes + sum(kv) *
kv_bytes_per_token``, priced by the roofline.  Plus the phantom-charge law
(empty set => exactly zero) and the monotonicity that keeps admission
deferral sane (more KV never gets cheaper).
"""

import dataclasses

import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
import hypothesis.strategies as st
from hypothesis import given, settings

from repro.configs.base import get_config
from repro.core.bridge import B300, TPU_V5E, BridgeModel
from repro.core.compute import ComputeModel
from repro.serving.kv_cache import RaggedBatch

CM = ComputeModel(get_config("qwen3p6-27b"), BridgeModel(B300, cc_on=True))
CM_OFF = ComputeModel(get_config("qwen1.5-4b"), BridgeModel(TPU_V5E,
                                                            cc_on=False))

kv_lens = st.lists(st.floats(min_value=0.0, max_value=65536.0,
                             allow_nan=False, allow_infinity=False),
                   min_size=0, max_size=256)


def _assert_closed_form(cm, lens):
    charge = cm.decode_charge(lens)
    flops = 2.0 * cm.active_params * len(lens)
    hbm = (cm.active_params * cm.bytes_per_param
           + sum(lens) * cm.kv_bytes_per_token())
    compute_s = cm.bridge.compute_time(flops, cm.spec.peak_flops)
    memory_s = cm.bridge.hbm_time(hbm, cm.spec.hbm_bw)
    assert charge.flops == flops
    assert charge.hbm_bytes == pytest.approx(hbm, rel=1e-12)
    assert charge.seconds == pytest.approx(max(compute_s, memory_s),
                                           rel=1e-12)
    assert charge.bound == ("compute" if compute_s >= memory_s else "memory")


@pytest.mark.parametrize("cm", [CM, CM_OFF], ids=["b300-on", "v5e-off"])
@settings(max_examples=200, deadline=None)
@given(batch=st.integers(min_value=1, max_value=4096),
       kv=st.floats(min_value=0.0, max_value=65536.0, allow_nan=False))
def test_uniform_lengths_match_the_closed_form(cm, batch, kv):
    _assert_closed_form(cm, [kv] * batch)


@pytest.mark.parametrize("cm", [CM, CM_OFF], ids=["b300-on", "v5e-off"])
@settings(max_examples=200, deadline=None)
@given(lens=kv_lens.filter(bool))
def test_ragged_lengths_match_the_closed_form(cm, lens):
    _assert_closed_form(cm, lens)


@settings(max_examples=100, deadline=None)
@given(lens=kv_lens)
def test_empty_is_zero_nonempty_is_positive(lens):
    charge = CM.decode_charge(lens)
    if not lens:
        assert charge.seconds == charge.flops == charge.hbm_bytes == 0.0
    else:
        assert charge.seconds > 0.0
        assert charge.flops > 0.0


@settings(max_examples=100, deadline=None)
@given(lens=kv_lens.filter(bool),
       extra=st.floats(min_value=1.0, max_value=65536.0, allow_nan=False))
def test_more_kv_never_cheaper(lens, extra):
    """Monotonicity: growing any slot's prefix (or adding a slot) can only
    add HBM traffic — the admission price never drops as work grows."""
    base = CM.decode_charge(lens)
    deeper = CM.decode_charge([lens[0] + extra] + lens[1:])
    wider = CM.decode_charge(lens + [extra])
    assert deeper.seconds >= base.seconds
    assert wider.seconds >= base.seconds


@settings(max_examples=100, deadline=None)
@given(lens=kv_lens)
def test_pricing_is_permutation_invariant(lens):
    """Packed pricing reads the KV *sum*: slot order cannot matter."""
    assert (CM.decode_charge(list(reversed(lens)))
            == CM.decode_charge(lens))


@settings(max_examples=100, deadline=None)
@given(pairs=st.lists(st.tuples(st.integers(min_value=0, max_value=511),
                                st.integers(min_value=0, max_value=65536)),
                      min_size=0, max_size=64))
def test_ragged_batch_invariants(pairs):
    batch = RaggedBatch.from_slots(pairs)
    assert batch.size == len(pairs)
    assert batch.total_kv_tokens == sum(k for _, k in pairs)
    offs = batch.offsets()
    assert offs[0] == 0 and offs[-1] == batch.total_kv_tokens
    assert all(offs[i] <= offs[i + 1] for i in range(batch.size))
    assert list(batch.slot_array()) == [s for s, _ in pairs]
