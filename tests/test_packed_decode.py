"""Packed ragged decode (DESIGN.md §10) + the phantom-charge fixes it exposed.

The laws pinned here:

  * empty ready set charges ZERO: ``decode_charge(0)``,
    ``decode_charge_masked([])`` and ``decode_charge_packed([])`` are all
    0 s / 0 flops / 0 bytes — the phantom-slot clamps (``max(1, ...)``) are
    gone;
  * unknown bridge-profile names raise instead of silently pricing the TPU
    v5e roofline (``spec_for_profile``); an explicit ``spec=`` still wins;
  * pricing parity: ``decode_charge_masked([k]*b)`` equals
    ``decode_charge(b, kv_len=k)`` exactly, and the packed charge equals
    the masked charge for equal per-slot lengths — one roofline, three
    entry points;
  * the packed engine produces byte-identical token streams to the dense
    path (greedy) at >= its virtual tok/s, emits DECODE_PACKED records
    with PACKED/DEFERRED tags, ships packed-sized prep/drain bytes, and
    keeps the deferral accounting of the masked path;
  * admission prices the READY set (masked-aware admission cost): a
    resident slot whose restore is still draining never inflates the
    deferral threshold, and an engine with nothing ready prices zero;
  * ``core.simulator`` derives its forward term from the same
    ``ComputeModel`` roofline (``forward_source == "roofline"``) without
    moving the calibrated paper-table numbers.
"""

import dataclasses
import hashlib
import json

import numpy as np
import pytest

from repro.configs.base import get_config, smoke_config
from repro.core.bridge import B300, TPU_V5E, BridgeModel, BridgeProfile
from repro.core.compute import (COMPUTE_SPECS, ComputeModel, spec_for_profile)
from repro.core.policy import (OffloadPolicy, SchedulingPolicy as SP,
                               cc_aware_defaults)
from repro.core.simulator import (Observation, fit_workload,
                                  roofline_forward_ms)
from repro.models.model import Model
from repro.serving.engine import Request, ServingEngine
from repro.serving.kv_cache import RaggedBatch, ragged_block_tables
from repro.serving.offload import HostBlock, OffloadManager
from repro.serving.sampler import SamplingParams
from repro.trace import TraceRecorder, check_tape
from repro.trace import opclasses as oc
from repro.trace.harness import GOLDEN, smoke_model


@pytest.fixture(scope="module")
def tiny_model():
    return smoke_model()


def _cm(profile=B300, cc_on=True, cfg_name="qwen3p6-27b"):
    return ComputeModel(get_config(cfg_name), BridgeModel(profile, cc_on=cc_on))


def _defaults(**overrides):
    return dataclasses.replace(cc_aware_defaults(True, concurrency=4),
                               **overrides)


def _zero(charge):
    return (charge.seconds == 0.0 and charge.flops == 0.0
            and charge.hbm_bytes == 0.0)


class TestPhantomCharges:
    """Satellite bugfix: an empty ready set must charge exactly zero."""

    def test_decode_charge_empty_batch_is_zero(self):
        assert _zero(_cm().decode_charge(0))
        assert _zero(_cm().decode_charge(0, kv_len=4096.0))

    def test_decode_charge_negative_batch_clamps_to_zero(self):
        assert _zero(_cm().decode_charge(-3))

    def test_decode_charge_masked_empty_is_zero(self):
        assert _zero(_cm().decode_charge_masked([]))

    def test_decode_charge_packed_empty_is_zero(self):
        assert _zero(_cm().decode_charge_packed([]))

    def test_step_seconds_helpers_follow(self):
        cm = _cm()
        assert cm.decode_step_s(0) == 0.0
        assert cm.decode_step_masked_s([]) == 0.0
        assert cm.decode_step_packed_s([]) == 0.0

    def test_nonempty_still_prices_the_weight_read_floor(self):
        """The fix removes the phantom, not the floor: one real slot still
        pays the full weight-read stream."""
        cm = _cm()
        one = cm.decode_charge(1)
        assert one.seconds > 0.0
        floor = (cm.active_params * cm.bytes_per_param
                 / COMPUTE_SPECS["b300-hgx"].hbm_bw)
        assert one.seconds >= floor

    def test_phantom_would_have_billed_a_whole_step(self):
        """Regression shape: the old clamp priced an empty set like one
        slot — ms-scale phantom time per idle poll."""
        cm = _cm()
        assert cm.decode_charge_masked([]).seconds < cm.decode_charge(1).seconds


class TestUnknownProfileSpec:
    """Satellite bugfix: unknown bridge profiles must raise, not silently
    price the TPU v5e roofline."""

    def test_every_builtin_profile_resolves(self):
        for name in COMPUTE_SPECS:
            assert spec_for_profile(name).hbm_bw > 0

    def test_unknown_profile_raises_with_known_list(self):
        ghost = dataclasses.replace(B300, name="gb999-ghost")
        with pytest.raises(ValueError, match="gb999-ghost") as ei:
            ComputeModel(get_config("qwen3p6-27b"),
                         BridgeModel(ghost, cc_on=True))
        assert "b300-hgx" in str(ei.value)      # names the known specs

    def test_explicit_spec_overrides(self):
        ghost = dataclasses.replace(B300, name="gb999-ghost")
        cm = ComputeModel(get_config("qwen3p6-27b"),
                          BridgeModel(ghost, cc_on=True),
                          spec=COMPUTE_SPECS["b300-hgx"])
        assert cm.decode_charge(4).seconds > 0.0

    def test_spec_for_profile_direct(self):
        with pytest.raises(ValueError, match="no ComputeSpec"):
            spec_for_profile("never-heard-of-it")


class TestPricingParity:
    """One roofline, three entry points: masked == dense for uniform
    lengths, packed == masked always."""

    @pytest.mark.parametrize("batch,kv", [(1, 0.0), (3, 512.0), (8, 1536.0),
                                          (64, 4096.0), (512, 128.0)])
    def test_masked_equals_dense_for_uniform_lengths(self, batch, kv):
        cm = _cm()
        masked = cm.decode_charge_masked([kv] * batch)
        dense = cm.decode_charge(batch, kv_len=kv)
        assert masked.seconds == pytest.approx(dense.seconds, rel=1e-12)
        assert masked.flops == dense.flops
        assert masked.hbm_bytes == pytest.approx(dense.hbm_bytes, rel=1e-12)
        assert masked.bound == dense.bound

    @pytest.mark.parametrize("lens", [[128.0], [1.0, 2.0, 3.0],
                                      [4096.0, 0.0, 512.0, 512.0],
                                      list(np.linspace(0, 2048, 33))])
    def test_packed_equals_masked_for_equal_lengths(self, lens):
        cm = _cm()
        packed = cm.decode_charge_packed(lens)
        masked = cm.decode_charge_masked(lens)
        assert packed == masked

    def test_ragged_prices_by_total_kv_not_width(self):
        """Packed pricing reads the KV sum, not batch * max: a ragged set
        charges strictly less than its dense-padded shape."""
        cm = _cm()
        ragged = cm.decode_charge_packed([4096.0, 64.0, 64.0, 64.0])
        padded = cm.decode_charge(4, kv_len=4096.0)
        assert ragged.seconds < padded.seconds


class TestRaggedBatch:
    def test_from_slots_preserves_order_and_lengths(self):
        b = RaggedBatch.from_slots([(3, 7), (0, 5), (2, 9)])
        assert b.slots == (3, 0, 2) and b.kv_lens == (7, 5, 9)
        assert b.size == 3 and b.total_kv_tokens == 21
        assert list(b.offsets()) == [0, 7, 12, 21]
        assert b.slot_array().dtype == np.int32

    def test_mismatched_lengths_rejected(self):
        with pytest.raises(ValueError, match="one kv_len per slot"):
            RaggedBatch(slots=(0, 1), kv_lens=(4,))

    def test_ragged_block_tables_csr(self):
        flat, offs = ragged_block_tables(
            {"a": [10, 11], "b": [20], "c": [30, 31, 32]}, ["a", "b", "c"])
        assert list(flat) == [10, 11, 20, 30, 31, 32]
        assert list(offs) == [0, 2, 3, 6]
        # packed total is the allocated pages; the dense shape pads to the
        # widest row (3) for every request
        assert flat.size == 6 < 3 * 3


def _ragged_engine(model, *, packed, max_batch=4, n_requests=6, seed=0,
                   **default_overrides):
    eng = ServingEngine(
        model, max_batch=max_batch, max_len=64, policy=SP.SYNC_DRAIN,
        bridge=BridgeModel(B300, cc_on=True),
        defaults=_defaults(packed_decode=packed, **default_overrides),
        seed=seed)
    for i in range(n_requests):
        eng.submit(Request(f"r{i}", prompt=[1, 2, 3 + (i % 5)],
                           sampling=SamplingParams(
                               max_new_tokens=3 + (i * 3) % 8)))
    return eng


class TestPackedEngine:
    def test_token_streams_identical_and_never_slower(self, tiny_model,
                                                      deterministic_seed):
        """The tentpole guarantee: packed == dense token streams (greedy)
        on a ragged workload, at >= the dense virtual tok/s."""
        def run(packed):
            eng = _ragged_engine(tiny_model, packed=packed,
                                 seed=deterministic_seed)
            stats = eng.run()
            toks = {r.request_id: list(r.output_tokens)
                    for r in eng.finished}
            eng.close()
            return toks, stats
        tok_p, p = run(True)
        tok_d, d = run(False)
        assert tok_p == tok_d
        assert p["finished"] == d["finished"] == 6
        tps_p = p["total_tokens"] / p["virtual_time_s"]
        tps_d = d["total_tokens"] / d["virtual_time_s"]
        assert tps_p >= tps_d

    def test_packed_records_on_tape(self, tiny_model, deterministic_seed):
        """Every packed step's compute lands as one DECODE_PACKED record
        tagged PACKED, and the stream conforms."""
        eng = _ragged_engine(tiny_model, packed=True, seed=deterministic_seed)
        with TraceRecorder(eng.gateway, label="packed") as rec:
            eng.run()
        eng.close()
        tape = rec.tape()
        mix = tape.op_class_mix()
        assert mix.get(oc.DECODE_PACKED, 0) == eng.step_count > 0
        assert oc.DECODE_COMPUTE not in mix and oc.DECODE_MASKED not in mix
        assert tape.tag_counts().get(oc.PACKED, 0) == eng.step_count
        packed_recs = [r for r in tape.records
                       if r.op_class == oc.DECODE_PACKED]
        assert all(r.is_compute and r.bound in ("compute", "memory")
                   for r in packed_recs)
        report = check_tape(tape)
        assert report.ok, report.format()

    def test_step_trace_packed_matches_active(self, tiny_model,
                                              deterministic_seed):
        eng = _ragged_engine(tiny_model, packed=True, seed=deterministic_seed)
        eng.run()
        eng.close()
        assert all(t.packed == t.active for t in eng.trace)
        # ragged finishes actually exercised sub-max widths
        assert {t.packed for t in eng.trace} > {eng.max_batch}

    def test_dense_trace_never_marks_packed(self, tiny_model,
                                            deterministic_seed):
        eng = _ragged_engine(tiny_model, packed=False,
                             seed=deterministic_seed)
        eng.run()
        eng.close()
        assert all(t.packed == 0 for t in eng.trace)

    def test_half_empty_engine_ships_packed_bytes(self, tiny_model,
                                                  deterministic_seed):
        """The bridge-byte win: 3 residents in an 8-slot engine prep and
        drain 3-row arrays, not max_batch-shaped ones."""
        def run(packed):
            eng = _ragged_engine(tiny_model, packed=packed, max_batch=8,
                                 n_requests=3, seed=deterministic_seed)
            eng.run()
            eng.close()
            return eng.trace
        tp = run(True)
        td = run(False)
        assert tp[0].active == td[0].active == 3
        assert tp[0].prep_bytes < td[0].prep_bytes
        assert tp[0].drain_bytes < td[0].drain_bytes
        assert td[0].drain_bytes == 8 * 4      # dense drains max_batch rows

    #: sha256 of the records (``to_dict``, keys sorted) and the virtual
    #: clock of ``test_pad_row_run_keeps_tokens_and_tape``'s run, captured
    #: from the engine as it was when it still moved the prep arrays it
    #: prices and uploaded the step inputs one by one
    PAD_RUN_TAPES = {
        SP.SYNC_DRAIN: ("32be01d3b4e95adc71265eff3a391c45"
                        "ed0fbadbe75ff0c8b74793d286fa77ec"),
        SP.WORKER_DRAIN: ("74a75db67215fd25a48c4d1ec02de697"
                          "ebc8423e7050e4830ff29bb6c17182d9"),
    }
    PAD_RUN_CLOCK = 0.002221757953601953

    @pytest.mark.parametrize("policy", [SP.SYNC_DRAIN, SP.WORKER_DRAIN])
    def test_pad_row_run_keeps_tokens_and_tape(self, tiny_model, policy):
        """Three requests in a four-slot engine: the three-row step runs at
        width 4 with a pad row, its inputs go up as one ``[3, 4]`` buffer,
        the greedy tokens equal the dense path's, and the bridge tape and
        clock equal those of the engine that moved every prep array."""
        def run(packed):
            eng = ServingEngine(
                tiny_model, max_batch=4, max_len=64, cc_on=True,
                policy=policy, seed=GOLDEN["seed"],
                defaults=dataclasses.replace(cc_aware_defaults(True),
                                             packed_decode=packed))
            step, widths = eng._packed_decode, []

            def spy(params, caches, tokens, index, slots):
                widths.append((len(set(np.asarray(slots).tolist())),
                               len(slots)))
                return step(params, caches, tokens, index, slots)
            eng._packed_decode = spy
            for i in range(3):
                eng.submit(Request(f"r{i}", prompt=[5, 7 + i, 11, 2 * i + 1],
                                   sampling=SamplingParams(
                                       max_new_tokens=4 + 2 * i)))
            with TraceRecorder(eng.gateway, label="pad") as rec:
                eng.run()
            eng.close()
            toks = {r.request_id: list(r.output_tokens) for r in eng.finished}
            return toks, rec.tape(), eng.clock.now, widths

        toks, tape, clock, widths = run(True)
        dense_toks, *_ = run(False)
        assert (3, 4) in widths
        assert toks == dense_toks and len(toks) == 3
        records = json.dumps([r.to_dict() for r in tape.records],
                             sort_keys=True)
        assert hashlib.sha256(records.encode()).hexdigest() == \
            self.PAD_RUN_TAPES[policy]
        assert clock == self.PAD_RUN_CLOCK

    def test_bucket_widths_are_pow2_capped_at_max_batch(self, tiny_model):
        eng = ServingEngine(tiny_model, max_batch=6, max_len=64,
                            cc_on=True, defaults=_defaults())
        try:
            assert [eng._bucket(n) for n in (1, 2, 3, 4, 5, 6)] == \
                [1, 2, 4, 4, 6, 6]
        finally:
            eng.close()

    def test_deferral_under_packed_keeps_tokens_and_tags(self, tiny_model,
                                                         deterministic_seed):
        """Slot masking composes with packing: a draining restore defers
        its slot out of the packed set, DEFERRED tags count deferred
        slot-steps, and the token streams still match the legacy run."""
        def run(packed):
            bridge = BridgeModel(B300, cc_on=True)
            eng = ServingEngine(
                tiny_model, max_batch=4, max_len=64, policy=SP.SYNC_DRAIN,
                bridge=bridge, defaults=_defaults(packed_decode=packed),
                compute_model=ComputeModel(get_config("qwen3p6-27b"), bridge),
                seed=deterministic_seed)
            eng.gateway.pool.prewarm()
            eng.submit(Request("r0", prompt=[1, 2, 3],
                               sampling=SamplingParams(max_new_tokens=4)))
            for i in range(1, 4):
                eng.submit(Request(f"r{i}", prompt=[1, 2, 3],
                                   sampling=SamplingParams(max_new_tokens=12)))
            eng.step()
            mgr = OffloadManager(eng.gateway, OffloadPolicy.REUSE_AWARE,
                                 pipelined_restore=True,
                                 restore_chunk_bytes=8 << 10)
            for b in range(96):
                mgr.host_store[b] = HostBlock(b, 128 << 10, 2, None)
            mgr.on_restore_done.append(eng.mark_restore)
            mgr.restore(list(range(96)), key="r0")
            with TraceRecorder(eng.gateway, label="packed-defer") as rec:
                stats = eng.run()
            eng.close()
            toks = {r.request_id: list(r.output_tokens)
                    for r in eng.finished}
            return toks, stats, eng, rec.tape()

        tok_p, stats_p, eng_p, tape = run(True)
        tok_d, stats_d, _, _ = run(False)
        assert tok_p == tok_d
        assert stats_p["overlap"]["deferred_slots"] > 0
        tags = tape.tag_counts()
        assert tags.get(oc.DEFERRED, 0) == sum(t.deferred
                                               for t in eng_p.trace) > 0
        # the recorder attached after the warm-up step, so the tape holds
        # one PACKED record per packed step except that first one
        assert tags.get(oc.PACKED, 0) == sum(1 for t in eng_p.trace
                                             if t.packed) - 1
        assert oc.DECODE_MASKED not in tape.op_class_mix()

    def test_packed_charge_prices_exactly_the_packed_rows(self, tiny_model,
                                                          deterministic_seed):
        """The tape's DECODE_PACKED durations equal decode_charge_packed of
        the per-step ready KV lengths — accounting at the REAL size even
        when the executed bucket is wider."""
        bridge = BridgeModel(B300, cc_on=True)
        eng = ServingEngine(
            tiny_model, max_batch=4, max_len=64, policy=SP.SYNC_DRAIN,
            bridge=bridge, defaults=_defaults(),
            compute_model=ComputeModel(get_config("qwen3p6-27b"), bridge),
            seed=deterministic_seed)
        for i in range(3):                     # 3 rows -> bucket width 4
            eng.submit(Request(f"r{i}", prompt=[1, 2, 3],
                               sampling=SamplingParams(max_new_tokens=3)))
        with TraceRecorder(eng.gateway, label="priced") as rec:
            eng.step()
            kv = [float(r.index) - 1 for r in eng.active.values()]
        eng.close()
        packed_recs = [r for r in rec.tape().records
                       if r.op_class == oc.DECODE_PACKED]
        assert len(packed_recs) == 1
        expected = ComputeModel(
            get_config("qwen3p6-27b"), bridge).decode_charge_packed(kv)
        assert packed_recs[0].duration_s == pytest.approx(expected.seconds,
                                                          rel=1e-12)

    def test_scales_into_the_hundreds(self, tiny_model, deterministic_seed):
        """Slot counts push past the dense comfort zone: a 128-slot engine
        with a ragged tail finishes every request and sweeps sub-max
        packed widths."""
        eng = ServingEngine(
            tiny_model, max_batch=128, max_len=32, policy=SP.SYNC_DRAIN,
            bridge=BridgeModel(B300, cc_on=True), defaults=_defaults(),
            seed=deterministic_seed)
        for i in range(128):
            eng.submit(Request(f"r{i}", prompt=[1 + (i % 7)],
                               sampling=SamplingParams(
                                   max_new_tokens=1 + (i % 5))))
        stats = eng.run()
        eng.close()
        assert stats["finished"] == 128
        widths = {t.packed for t in eng.trace}
        assert max(widths) == 128 and len(widths) > 1


class TestMaskedAwareAdmission:
    """Satellite: admission prices the ready set, not every resident."""

    def _engine(self, model, seed, **overrides):
        eng = ServingEngine(
            model, max_batch=4, max_len=64, policy=SP.SYNC_DRAIN,
            cc_on=True, defaults=_defaults(**overrides), seed=seed)
        eng.gateway.pool.prewarm()
        return eng

    def test_ready_lens_empty_engine(self, tiny_model, deterministic_seed):
        eng = self._engine(tiny_model, deterministic_seed)
        try:
            assert eng._ready_lens() == []
            assert eng.compute.decode_step_masked_s(eng._ready_lens()) == 0.0
        finally:
            eng.close()

    def test_ready_lens_excludes_draining_slot(self, tiny_model,
                                               deterministic_seed):
        eng = self._engine(tiny_model, deterministic_seed)
        try:
            for rid in ("r0", "r1"):
                eng.submit(Request(rid, prompt=[1, 2, 3],
                                   sampling=SamplingParams(max_new_tokens=8)))
            eng.step()                         # both running
            all_lens = eng._ready_lens()
            assert len(all_lens) == 2
            # r0's restore starts draining: it leaves the priced ready set
            eng.mark_restore("r0", eng.clock.now + 10.0)
            lens = eng._ready_lens()
            r1_index = float(next(r.index for r in eng.active.values()
                                  if r.request_id == "r1"))
            assert lens == [r1_index]
            # and with both draining the admission price is honestly zero
            eng.mark_restore("r1", eng.clock.now + 10.0)
            assert eng._ready_lens() == []
            assert eng.compute.decode_step_masked_s([]) == 0.0
        finally:
            eng.close()

    def test_legacy_flag_off_prices_all_residents(self, tiny_model,
                                                  deterministic_seed):
        eng = self._engine(tiny_model, deterministic_seed,
                           slot_masked_decode=False)
        try:
            for rid in ("r0", "r1"):
                eng.submit(Request(rid, prompt=[1, 2, 3],
                                   sampling=SamplingParams(max_new_tokens=8)))
            eng.step()
            eng.mark_restore("r0", eng.clock.now + 10.0)
            assert len(eng._ready_lens()) == 2  # whole-batch semantics
        finally:
            eng.close()


#: the §5.1 b300 c=128 paper cells the serving workload is calibrated on
_PAPER_OBS = [
    Observation(SP.ASYNC_OVERLAP, False, tpot_ms=23.64),
    Observation(SP.ASYNC_OVERLAP, True, tpot_ms=31.10),
    Observation(SP.SYNC_DRAIN, False, tpot_ms=26.56),
    Observation(SP.SYNC_DRAIN, True, tpot_ms=26.92),
]


class TestSimulatorRooflineFusion:
    """Satellite/tentpole: one pricing source — the simulator's forward
    term is the ComputeModel roofline times a fitted efficiency."""

    def test_fit_reports_roofline_source_and_eff(self):
        cfg = get_config("qwen3p6-27b")
        w = fit_workload("fused", 128, B300, _PAPER_OBS, cfg=cfg,
                         kv_len=1536.0)
        assert w.forward_source == "roofline"
        assert w.roofline_eff > 0.0
        base = roofline_forward_ms(cfg, B300, 128, kv_len=1536.0)
        assert w.forward_ms == pytest.approx(w.roofline_eff * base, rel=1e-9)

    def test_fit_without_cfg_stays_calibrated(self):
        w = fit_workload("legacy", 128, B300, _PAPER_OBS)
        assert w.forward_source == "calibrated"
        assert w.roofline_eff == 0.0

    def test_reparameterization_is_numerically_identical(self):
        """Same linear space, same optimum: the roofline-anchored fit
        predicts the same forward_ms as the legacy free fit."""
        legacy = fit_workload("legacy", 128, B300, _PAPER_OBS)
        fused = fit_workload("fused", 128, B300, _PAPER_OBS,
                             cfg=get_config("qwen3p6-27b"), kv_len=1536.0)
        assert fused.forward_ms == pytest.approx(legacy.forward_ms, rel=1e-6)
        assert fused.prep_cpu_ms == pytest.approx(legacy.prep_cpu_ms,
                                                  rel=1e-6)
        assert fused.gpu_stream_gain_ms == pytest.approx(
            legacy.gpu_stream_gain_ms, abs=1e-6)

    def test_roofline_forward_ms_prices_cc_off(self):
        """The anchor is the CC-off roofline: CC parity enters through the
        simulator's bridge terms, never double-counted in the anchor."""
        cfg = get_config("qwen3p6-27b")
        ms = roofline_forward_ms(cfg, B300, 64, kv_len=1536.0)
        cm = ComputeModel(cfg, BridgeModel(B300, cc_on=False))
        assert ms == pytest.approx(
            cm.decode_step_s(64, kv_len=1536.0) * 1e3, rel=1e-12)
