"""The trace reduction: on a trace recorded on a TPU v5e (a jitted matmul
under the scope ``KERNEL_probe_matmul``, run three times under
``bench.tick`` spans; the checkout's path in its source locations is
replaced by ``<checkout>``), and on a small trace laid out as the profiler
records one TPU (a host plane with the harness's spans and runtime events, a
device plane with an ``XLA Modules`` and an ``XLA Ops`` line whose ops carry
their HLO text), read by the metric readers."""

from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import chipbench_testcell
from chipbench import harness
from chipbench import trace_reduce as tr
from chipbench.loop import Tick

RECORDED = Path(__file__).parent / "data" / "probe.xplane.pb"
METRICS = chipbench_testcell.REPO / "chipbench" / "metrics"
PAGED = "jit(_unknown)/while/body/KERNEL_paged_attention/dot_general"


def test_recorded_trace_programs_and_scopes():
    assert list(tr.op_scopes(str(RECORDED)).values()) == [
        "jit(step)/KERNEL_probe_matmul/dot_general"]
    red = tr.reduce(str(RECORDED))
    assert red.planes == 1
    s, n = red.module_time(r"^step$")
    assert n == 3 and s == pytest.approx(23.424e-6)
    assert red.programs == {4130390087658909887: [23424.0, 3]}
    scoped = red.scoped("KERNEL_probe_matmul")
    assert [o.label for o in scoped] == ["fusion bf16[1024]"] * 3
    assert {o.program for o in scoped} == {4130390087658909887}
    assert red.program_time({o.program for o in scoped}) == (
        pytest.approx(23.424e-6), 3)


def test_recorded_trace_busy_and_gaps():
    red = tr.reduce(str(RECORDED))
    # three runs of one matmul fusion and its weight prefetch
    assert red.busy_s == pytest.approx(23.403e-6)
    ops = dict(red.breakdown()["device_ops"])
    assert ops["step:fusion bf16[1024]"] == pytest.approx(23.357e-6)
    gaps = dict(red.breakdown()["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(red.window_s - red.busy_s)
    assert any(k.startswith("bench.idle/") for k in gaps)

US = 1_000


def ev(name, start_us, dur_us, **stats):
    return NS(name=name, start_ns=start_us * US, duration_ns=dur_us * US,
              stats=list(stats.items()))


def trace():
    host = NS(name="/host:CPU", lines=[
        NS(name="python3", events=[
            ev("bench.window", 100, 1000),
            ev("bench.tick", 100, 500),
            ev("PjitFunction(packed_step)", 120, 30),
            ev("bench.stamp", 600, 100),
            ev("bench.idle", 700, 400),
        ])])
    ops = [
        # before the window: left out
        ev("%fusion.1 = bf16[8,2048]{1,0} fusion(%a), kind=kOutput", 50, 20),
        # a loop over layers holds the ops after it: busy counts it once,
        # the breakdown not at all
        ev("%while.2 = (s32[], bf16[8,2048]{1,0}) while(%t)", 150, 170),
        ev("%fusion.3 = bf16[8,2048]{1,0} fusion(%a), kind=kOutput", 150, 100),
        ev("%copy.4 = bf16[16,8,1024]{2,1,0} copy(%c)", 250, 50),
        ev("%copy.5 = bf16[16,8,1024]{2,1,0} copy(%d)", 280, 40),
        ev("%fusion.6 = f32[1,50304]{1,0} fusion(%e), kind=kOutput", 500, 10),
        ev("%fusion.7 = f32[1,50304]{1,0} fusion(%e), kind=kOutput", 510, 5),
        # after the window closes at 1100
        ev("%fusion.8 = bf16[8,2048]{1,0} fusion(%a), kind=kOutput", 1200, 10),
    ]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            ev("jit_packed_step(12)", 40, 40),
            ev("jit_packed_step(12)", 150, 170),
            ev("jit_prefill_logits_cache(7)", 500, 15),
            ev("jit_packed_step(12)", 1200, 10)]),
        NS(name="XLA Ops", events=ops)])
    return [NS(name="/host:metadata", lines=[]), host, device]


def scopes():
    return {"%fusion.3 = bf16[8,2048]{1,0} fusion(%a), kind=kOutput": PAGED,
            "%copy.4 = bf16[16,8,1024]{2,1,0} copy(%c)": PAGED,
            "%fusion.6 = f32[1,50304]{1,0} fusion(%e), kind=kOutput":
                "jit(prefill_logits_cache)/dot_general"}


def test_busy_modules_and_scopes():
    red = tr.reduce_planes(trace(), host_window_s=(1.0, 2.0))
    assert red.window_s == pytest.approx(1000e-6)
    # 150..320 and 500..515 busy
    assert red.busy_s == pytest.approx(185e-6)
    assert red.module_time("packed_step") == (pytest.approx(170e-6), 1)
    assert red.module_time("prefill_logits_cache") == (pytest.approx(15e-6), 1)
    ops = dict(red.breakdown()["device_ops"])
    assert ops == pytest.approx({
        "packed_step:fusion bf16[8,2048]": 100e-6,
        "packed_step:copy bf16[16,8,1024]": 90e-6,
        "prefill_logits_cache:fusion f32[1,50304]": 15e-6})


def test_idle_gaps_are_named_by_the_host():
    red = tr.reduce_planes(trace())
    gaps = dict(red.breakdown()["idle_gaps"])
    # 100..150 inside the tick; 320..500 tick; 515..1100 mostly idle
    assert gaps["bench.tick/PjitFunction(packed_step)"] == pytest.approx(50e-6)
    assert gaps["bench.tick/-"] == pytest.approx(180e-6)
    assert gaps["bench.idle/-"] == pytest.approx(585e-6)
    assert sum(gaps.values()) == pytest.approx(red.window_s - red.busy_s)


def test_ticks_inside_the_traced_window():
    red = tr.reduce_planes(trace(), host_window_s=(1.0, 2.0))
    ticks = [Tick(0.5, 1.1), Tick(1.1, 1.5), Tick(1.9, 2.1)]
    assert red.ticks(ticks) == [ticks[1]]


def test_labels():
    assert tr.module_name("jit_packed_step(123)") == "packed_step"
    assert tr.op_label("%copy.250 = bf16[16,8,1024,16,128]{4,3,2,1,0:T(8,128)"
                       "(2,1)} copy(bf16[16,8,1024,16,128] %p)") == \
        "copy bf16[16,8,1024,16,128]"
    assert tr.op_label("%convert_reduce_fusion.5 = f32[8]{0} fusion(%x)") == \
        "convert_reduce_fusion f32[8]"



def _run(red, ticks=()):
    model = {"n_layers": 2, "n_heads": 4, "n_kv_heads": 2, "head_dim": 8,
             "dtype": "bfloat16"}
    return NS(trace=red, window=NS(ticks=list(ticks)), model=model,
              device_kind="TPU v5 lite")


def _read(name, run):
    return harness._module(METRICS / f"{name}.py").read(run)


def test_packed_step_is_found_by_its_decode_attention():
    red = tr.reduce_planes(trace(), host_window_s=(1.0, 2.0), scopes=scopes())
    assert {o.program for o in red.scoped("KERNEL_paged_attention")} == {12}
    # the one run of program 12 inside the window, 170 us
    assert _read("decode_device_ms", _run(red)) == pytest.approx(0.170)
    # no scopes, no program to read
    assert _read("decode_device_ms",
                 _run(tr.reduce_planes(trace(), (1.0, 2.0)))) is None
    assert _read("prefill_ms", _run(red)) == pytest.approx(0.015)


def test_decode_attention_roofline():
    from chipbench import flops
    red = tr.reduce_planes(trace(), host_window_s=(1.0, 2.0), scopes=scopes())
    ticks = [Tick(1.1, 1.5, decode_kv=[100, 300])]
    run = _run(red, ticks)
    # fusion.3 150..250 and copy.4 250..300: 150 us under the scope
    least = (flops.attention_bytes(run.model, 100)
             + flops.attention_bytes(run.model, 300)) / 819e9
    assert _read("decode_attention_roofline", run) == pytest.approx(
        100 * least / 150e-6)
    assert flops.attention_bytes(run.model, 100) == 2 * 2 * 8 * 2 * 100 * 2
    # no decode rows in the traced ticks: nothing to read
    assert _read("decode_attention_roofline", _run(red, [])) is None
