"""Packed ragged decode (DESIGN.md §10) + the phantom-charge fixes it exposed.

The laws pinned here:

  * empty ready set charges ZERO: ``decode_charge([])`` is 0 s / 0 flops /
    0 bytes — the phantom-slot clamp (``max(1, ...)``) is gone;
  * unknown bridge-profile names raise instead of silently pricing the TPU
    v5e roofline (``spec_for_profile``); an explicit ``spec=`` still wins;
  * the one decode charge is the closed form: FLOPs ``2 * params * n``,
    HBM ``params * bytes + sum(kv) * kv_bytes_per_token``;
  * the packed engine produces the straight-line greedy loop's token
    streams, emits DECODE_PACKED records with PACKED/DEFERRED tags, ships
    packed-sized prep/drain bytes, and keeps the deferral accounting of
    the masked path;
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

import jax
import numpy as np
import pytest

from repro.configs.base import get_config, smoke_config
from repro.core.bridge import B300, TPU_V5E, BridgeModel, BridgeProfile
from repro.core.compute import (COMPUTE_SPECS, ComputeModel, spec_for_profile)
from repro.core.policy import (OffloadPolicy, SchedulingPolicy as SP,
                               cc_aware_defaults)
from repro.core.simulator import (Observation, fit_workload,
                                  roofline_forward_ms)
from repro.launch.serve import greedy_reference
from repro.models.model import Model
from repro.resilience import DegradationLadder, FaultInjector, FaultPlan
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

    @pytest.mark.parametrize("empty", [[], (), np.zeros(0)],
                             ids=["list", "tuple", "ndarray"])
    def test_decode_charge_empty_batch_is_zero(self, empty):
        assert _zero(_cm().decode_charge(empty))

    def test_decode_charge_negative_kv_length_clamps_to_zero(self):
        cm = _cm()
        assert cm.decode_charge([-3.0, 512.0]) == cm.decode_charge([0.0,
                                                                    512.0])

    def test_step_seconds_helpers_follow(self):
        cm = _cm()
        assert cm.decode_step_s([]) == 0.0
        assert cm.decode_step_s([512.0]) == cm.decode_charge([512.0]).seconds

    def test_nonempty_still_prices_the_weight_read_floor(self):
        """The fix removes the phantom, not the floor: one real slot still
        pays the full weight-read stream."""
        cm = _cm()
        one = cm.decode_charge([0.0])
        assert one.seconds > 0.0
        floor = (cm.active_params * cm.bytes_per_param
                 / COMPUTE_SPECS["b300-hgx"].hbm_bw)
        assert one.seconds >= floor

    def test_phantom_would_have_billed_a_whole_step(self):
        """Regression shape: the old clamp priced an empty set like one
        slot — ms-scale phantom time per idle poll."""
        cm = _cm()
        assert cm.decode_charge([]).seconds < cm.decode_charge([0.0]).seconds


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
        assert cm.decode_charge([0.0] * 4).seconds > 0.0

    def test_spec_for_profile_direct(self):
        with pytest.raises(ValueError, match="no ComputeSpec"):
            spec_for_profile("never-heard-of-it")


def _closed_form(cm, lens):
    """(FLOPs, HBM bytes) of one decode step over per-slot KV ``lens``:
    every active param once per row, the weights streamed once, and each
    row's cached prefix read."""
    return (2.0 * cm.active_params * len(lens),
            cm.active_params * cm.bytes_per_param
            + sum(lens) * cm.kv_bytes_per_token())


def _assert_closed_form(cm, lens):
    charge = cm.decode_charge(lens)
    flops, hbm = _closed_form(cm, lens)
    assert charge.flops == flops
    assert charge.hbm_bytes == pytest.approx(hbm, rel=1e-12)
    compute_s = cm.bridge.compute_time(flops, cm.spec.peak_flops)
    memory_s = cm.bridge.hbm_time(hbm, cm.spec.hbm_bw)
    assert charge.seconds == pytest.approx(max(compute_s, memory_s),
                                           rel=1e-12)
    assert charge.bound == ("compute" if compute_s >= memory_s else "memory")


class TestPricingParity:
    """One roofline, one entry point: the charge is the closed form, for
    uniform and ragged per-slot lengths alike."""

    @pytest.mark.parametrize("batch,kv", [(1, 0.0), (3, 512.0), (8, 1536.0),
                                          (64, 4096.0), (512, 128.0)])
    def test_uniform_lengths_match_the_closed_form(self, batch, kv):
        _assert_closed_form(_cm(), [kv] * batch)

    @pytest.mark.parametrize("lens", [[128.0], [1.0, 2.0, 3.0],
                                      [4096.0, 0.0, 512.0, 512.0],
                                      list(np.linspace(0, 2048, 33))])
    def test_ragged_lengths_match_the_closed_form(self, lens):
        _assert_closed_form(_cm(), lens)

    def test_ragged_prices_by_total_kv_not_width(self):
        """Packed pricing reads the KV sum, not batch * max: a ragged set
        charges strictly less than its dense-padded shape."""
        cm = _cm()
        ragged = cm.decode_charge([4096.0, 64.0, 64.0, 64.0])
        padded = cm.decode_charge([4096.0] * 4)
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


def _ragged_engine(model, *, max_batch=4, n_requests=6, seed=0):
    eng = ServingEngine(
        model, max_batch=max_batch, max_len=64, policy=SP.SYNC_DRAIN,
        bridge=BridgeModel(B300, cc_on=True), defaults=_defaults(),
        seed=seed)
    for i in range(n_requests):
        eng.submit(Request(f"r{i}", prompt=[1, 2, 3 + (i % 5)],
                           sampling=SamplingParams(
                               max_new_tokens=3 + (i * 3) % 8)))
    return eng


def _reference_tokens(model, requests, seed):
    """Each request's tokens from the straight-line greedy loop over all
    prompts at once, cut to the request's own budget."""
    budget = max(r.sampling.max_new_tokens for r in requests)
    ref, finite = greedy_reference(
        model.cfg, model.init(jax.random.PRNGKey(seed)),
        [r.prompt for r in requests], max_new_tokens=budget, max_len=64)
    assert finite
    return {r.request_id: toks[:r.sampling.max_new_tokens]
            for r, toks in zip(requests, ref)}


def _full_width(eng):
    """Hold ``eng``'s ladder at its last rung: every step runs at the
    fixed full width."""
    ladder = DegradationLadder(recovery_quiet_s=float("inf"))
    FaultInjector(FaultPlan(seed=0), ladder=ladder).attach(eng.gateway)
    ladder.observe_fault(eng.clock.now)
    for _ in range(3):
        ladder.escalate(eng.clock.now)
    assert ladder.dense_step_forced


class TestPackedEngine:
    def test_token_streams_identical_and_never_slower(self, tiny_model,
                                                      deterministic_seed):
        """The tentpole guarantee: on a ragged workload the packed engine
        gives the straight-line greedy loop's tokens, and its bucketed
        widths run at >= the virtual tok/s of the fixed full width."""
        def run(full_width):
            eng = _ragged_engine(tiny_model, seed=deterministic_seed)
            if full_width:
                _full_width(eng)
            requests = list(eng.queue)
            stats = eng.run()
            toks = {r.request_id: list(r.output_tokens)
                    for r in eng.finished}
            eng.close()
            return toks, stats, requests
        tok_p, p, requests = run(False)
        tok_f, f, _ = run(True)
        assert tok_p == tok_f == _reference_tokens(tiny_model, requests,
                                                   deterministic_seed)
        assert p["finished"] == f["finished"] == 6
        tps_p = p["total_tokens"] / p["virtual_time_s"]
        tps_f = f["total_tokens"] / f["virtual_time_s"]
        assert tps_p >= tps_f

    def test_packed_records_on_tape(self, tiny_model, deterministic_seed):
        """Every packed step's compute lands as one DECODE_PACKED record
        tagged PACKED, and the stream conforms."""
        eng = _ragged_engine(tiny_model, seed=deterministic_seed)
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
        eng = _ragged_engine(tiny_model, seed=deterministic_seed)
        eng.run()
        eng.close()
        assert all(t.packed == t.active for t in eng.trace)
        # ragged finishes actually exercised sub-max widths
        assert {t.packed for t in eng.trace} > {eng.max_batch}

    def test_half_empty_engine_ships_packed_bytes(self, tiny_model,
                                                  deterministic_seed):
        """The bridge-byte win: 3 residents in an 8-slot engine prep and
        drain 3-row arrays, not max_batch-shaped ones."""
        eng = _ragged_engine(tiny_model, max_batch=8, n_requests=3,
                             seed=deterministic_seed)
        eng.run()
        eng.close()
        first = eng.trace[0]
        assert first.active == first.packed == 3
        # six int32 prep arrays of 3 rows (tokens, positions and four
        # modelled step inputs), and 3 int32 tokens drained
        assert first.prep_bytes == 6 * 3 * 4
        assert first.drain_bytes == 3 * 4

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
        the greedy tokens equal the straight-line loop's, and the bridge
        tape and clock equal those of the engine that moved every prep
        array."""
        eng = ServingEngine(
            tiny_model, max_batch=4, max_len=64, cc_on=True,
            policy=policy, seed=GOLDEN["seed"],
            defaults=cc_aware_defaults(True))
        step, widths = eng._packed_decode, []

        def spy(params, caches, tokens, index, slots):
            widths.append((len(set(np.asarray(slots).tolist())), len(slots)))
            return step(params, caches, tokens, index, slots)
        eng._packed_decode = spy
        for i in range(3):
            eng.submit(Request(f"r{i}", prompt=[5, 7 + i, 11, 2 * i + 1],
                               sampling=SamplingParams(
                                   max_new_tokens=4 + 2 * i)))
        requests = list(eng.queue)
        with TraceRecorder(eng.gateway, label="pad") as rec:
            eng.run()
        eng.close()
        toks = {r.request_id: list(r.output_tokens) for r in eng.finished}
        tape, clock = rec.tape(), eng.clock.now
        assert (3, 4) in widths
        assert toks == _reference_tokens(tiny_model, requests, GOLDEN["seed"])
        assert len(toks) == 3
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
        slot-steps, and the token streams still match a run with no
        restore."""
        def run(restore):
            bridge = BridgeModel(B300, cc_on=True)
            eng = ServingEngine(
                tiny_model, max_batch=4, max_len=64, policy=SP.SYNC_DRAIN,
                bridge=bridge, defaults=_defaults(),
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
            if restore:
                mgr.restore(list(range(96)), key="r0")
            with TraceRecorder(eng.gateway, label="packed-defer") as rec:
                stats = eng.run()
            eng.close()
            toks = {r.request_id: list(r.output_tokens)
                    for r in eng.finished}
            return toks, stats, eng, rec.tape()

        tok_p, stats_p, eng_p, tape = run(True)
        tok_free, stats_free, _, _ = run(False)
        assert tok_p == tok_free
        assert stats_p["overlap"]["deferred_slots"] > 0
        assert stats_free["overlap"]["deferred_slots"] == 0
        tags = tape.tag_counts()
        assert tags.get(oc.DEFERRED, 0) == sum(t.deferred
                                               for t in eng_p.trace) > 0
        # the recorder attached after the warm-up step, so the tape holds
        # one PACKED record per packed step except that first one
        assert tags.get(oc.PACKED, 0) == len(eng_p.trace) - 1
        assert oc.DECODE_MASKED not in tape.op_class_mix()

    def test_packed_charge_prices_exactly_the_packed_rows(self, tiny_model,
                                                          deterministic_seed):
        """The tape's DECODE_PACKED durations equal decode_charge of
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
            get_config("qwen3p6-27b"), bridge).decode_charge(kv)
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
            assert eng.compute.decode_step_s(eng._ready_lens()) == 0.0
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
            assert eng.compute.decode_step_s([]) == 0.0
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
            cm.decode_step_s([1536.0] * 64) * 1e3, rel=1e-12)
