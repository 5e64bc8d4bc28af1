"""DeepSeek-V2-Lite's cell at a small size on the CPU: the reference draws
the program's weights, the engine's prefill and packed decode steps give the
reference's logits, a tiny cell of the configuration runs through the
harness and comes out correct, and the cell's metric readers and operation
counts read what they should from a synthetic trace and span ring (and
nothing from a program without the spans and scopes they read)."""

from __future__ import annotations

import json
import shutil
import time
from pathlib import Path
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_testcell
from chipbench import flops_mla_moe as fm
from chipbench import harness, refcore, spans
from chipbench import trace_reduce as tr
from chipbench.loop import Tick
from repro.obs import wall

REPO = chipbench_testcell.REPO
CONFIG = REPO / "chipbench" / "configs" / "dsv2lite.json"
METRICS = REPO / "chipbench" / "metrics"
#: V2-Lite's block at 128 wide: one dense layer then two MoE layers of 8
#: routed experts (top-6, 2 shared) of which experts 4-7 are held here
SMALL = dict(n_layers=3, d_model=128, n_heads=4, kv_lora_rank=32,
             rope_head_dim=16, nope_head_dim=32, v_head_dim=32, d_ff=256,
             d_expert=64, n_routed_experts=8, n_experts_held=4,
             expert_offset=4, vocab_size=512)
#: the program in float32 against the float32 reference: the engine's
#: logits match to about 1e-5 (summation order: absorbed against
#: decompressed attention, blockwise prefill); a reference whose matmul
#: operands are rounded to bf16, or gates rescaled to sum to one, misses by
#: more than 1e-2
TOL = 2e-4


def _small(dtype="float32"):
    config = json.loads(CONFIG.read_text())
    config["model"].update(SMALL, dtype=dtype, logits_dtype=dtype,
                           attn_impl="dense")
    config["max_len"] = 64
    return config, harness._module(CONFIG.with_suffix(".py"))


def test_reference_draws_the_programs_weights():
    from repro.models.model import Model
    config, ref = _small("bfloat16")
    cfg = harness.model_config(config)
    key = jax.random.PRNGKey(harness.engine_seed(3))
    prog = Model(cfg).init(key)
    mine = ref.init(key, config["model"])
    val = lambda p: np.asarray(p.value, np.float32)
    same = lambda a, b, what: np.testing.assert_array_equal(
        np.asarray(a, np.float32), b, err_msg=what)
    same(mine["embed"], val(prog["embed"]), "embed")
    same(mine["unembed"], val(prog["unembed"]), "unembed")
    for k in ("wq", "wdkv", "wuk", "wuv", "wo"):
        same(mine["block0"]["attn"][k], val(prog["block0"]["attn"][k]), k)
    for k in ("wi", "wg", "wo"):
        same(mine["block0"]["mlp"][k], val(prog["block0"]["mlp"][k]), k)
    blocks = prog["blocks"]
    assert mine["layers"]["wi"].shape[:2] == (2, 4)
    for k in ("wq", "wdkv", "wuk", "wuv", "wo"):
        same(mine["layers"]["attn"][k], val(blocks["attn"][k]), k)
    for k in ("router", "wi", "wg", "wo"):
        same(mine["layers"][k], val(blocks["mlp"][k]), k)
    assert mine["layers"]["router"].dtype == jnp.float32
    for k in ("wi", "wg", "wo"):
        same(mine["layers"]["shared"][k], val(blocks["mlp"]["shared"][k]), k)


class _Bf16(refcore.Matmul):
    """The reference with its matmul operands rounded to bf16."""

    def __call__(self, a, w):
        r = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
        return super().__call__(r(a), r(w))


def _served_logits(config, prompts, max_new, monkeypatch):
    """Serve ``prompts`` through ``ServingEngine`` and record the logits of
    its prompt programs and packed steps: per request, its served tokens and
    {position: logits} of the programs that produced them."""
    from repro.models.model import Model
    from repro.serving import engine as engine_mod
    from repro.serving.engine import Request, ServingEngine
    from repro.serving.sampler import SamplingParams
    prefill, rows = {}, []
    real_prefill = engine_mod.prefill_logits_cache

    def prefill_spy(cfg, params, tokens, max_len):
        out = real_prefill(cfg, params, tokens, max_len)
        prefill[tuple(np.asarray(tokens[0]).tolist())] = np.asarray(out[0])
        return out

    monkeypatch.setattr(engine_mod, "prefill_logits_cache", prefill_spy)
    eng = ServingEngine(Model(harness.model_config(config)), max_batch=4,
                        max_len=config["max_len"], seed=11)
    step = eng._packed_decode

    def step_spy(params, caches, tokens, index, slots):
        out = step(params, caches, tokens, index, slots)
        rows.append((np.asarray(slots), np.asarray(index),
                     np.asarray(tokens), np.asarray(out[0])))
        return out

    eng._packed_decode = step_spy
    reqs = [Request(f"r{i}", prompt=p,
                    sampling=SamplingParams(max_new_tokens=max_new))
            for i, p in enumerate(prompts)]
    slot_of = {}
    admit = eng._prefill_into_slot

    def admit_spy(req, slot):
        slot_of[req.request_id] = slot
        admit(req, slot)

    eng._prefill_into_slot = admit_spy
    for r in reqs:
        eng.submit(r)
    eng.run()
    out = []
    for r in reqs:
        seen = {len(r.prompt) - 1: prefill[tuple(r.prompt)][0, -1]}
        for slots, index, tokens, logits in rows:
            for i in np.flatnonzero(slots == slot_of[r.request_id]):
                seq = r.prompt + r.output_tokens
                assert tokens[i, 0] == seq[index[i]]
                seen[int(index[i])] = logits[i, -1]
        out.append((r, seen))
    return out


def test_engine_logits_equal_the_references(monkeypatch):
    """Prefill, then seven packed decode steps through the engine (three
    requests, a bucket pad row): every program's logits against the
    reference's full forward over the served sequence.  A bf16 reference
    and a program that renormalises its gates both miss."""
    config, ref = _small()
    c = config["model"]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 512, n).tolist() for n in (5, 9, 12)]
    served = _served_logits(config, prompts, 8, monkeypatch)
    params = ref.init(jax.random.PRNGKey(11), c)
    exact, low = refcore.Matmul("exact"), _Bf16()
    renorm_config = json.loads(json.dumps(config))
    renorm_config["model"]["norm_topk_prob"] = True
    renormed = _served_logits(renorm_config, prompts, 8, monkeypatch)
    worst = {"program": 0.0, "bf16": 0.0, "renorm": 0.0}
    for (r, seen), (r2, seen2) in zip(served, renormed):
        assert len(r.output_tokens) == 8 and len(seen) == 8
        seq = jnp.asarray(r.prompt + r.output_tokens[:-1])
        want = np.asarray(ref.forward(params, seq, c, exact))
        bf16 = np.asarray(ref.forward(params, seq, c, low))
        for pos, got in seen.items():
            worst["program"] = max(worst["program"],
                                   float(np.abs(got - want[pos]).max()))
            worst["bf16"] = max(worst["bf16"],
                                float(np.abs(bf16[pos] - want[pos]).max()))
        # the renormalising program's first token (its prompt's logits)
        pos = len(r2.prompt) - 1
        worst["renorm"] = max(worst["renorm"],
                              float(np.abs(seen2[pos] - want[pos]).max()))
    assert worst["program"] < TOL, worst
    assert worst["bf16"] > 1e-2 and worst["renorm"] > 1e-2, worst


def _tiny_root(root: Path) -> Path:
    """A benchmark root holding one tiny cell of this configuration."""
    root = chipbench_testcell.make_root(root)
    cb = root / "chipbench"
    shutil.copy(CONFIG.with_suffix(".py"), cb / "configs" / "tinymoe.py")
    config, _ = _small("bfloat16")
    config.update(name="tinymoe")
    (cb / "configs" / "tinymoe.json").write_text(json.dumps(config))
    (cb / "cells" / "tinymoe.closed.json").write_text(json.dumps(dict(
        chipbench_testcell.SAMPLE, max_logit_gap=chipbench_testcell.LIMIT)))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="tinymoe",
                                 file="chipbench/configs/tinymoe.json"))
    bench["workloads"].append({"name": "tinymoe.closed", "config": "tinymoe",
                               "traffic": "closed", "chips": 1,
                               "why": "tiny closed loop"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_a_tiny_cell_of_the_configuration_is_correct(tmp_path, monkeypatch):
    """Built, warmed, served and checked by the harness as on the chip: the
    served tokens sit within the limit of the reference's best, the fp8
    control does not."""
    from repro.launch import serve
    monkeypatch.setattr(serve, "use_compile_cache", lambda: None)
    root = _tiny_root(tmp_path)
    cell = harness.load_cell(root, "tinymoe.closed")
    assert harness.model_config(cell.config).experts_held == 4
    out = harness.run_cell(root, "tinymoe.closed", 2**33 + 5, 1.5, False,
                           t_process=time.perf_counter(), control=True)
    assert out["result"]["correct"], out["result"]["checks"]
    assert out["readings"]["control_correct"] is False


# -- operation counts ----------------------------------------------------------

def test_operation_counts_at_the_published_size():
    c = json.loads(CONFIG.read_text())["model"]
    # a latent row: (512 + 64) bf16 in each of 27 layers
    assert fm.mla_decode_bytes(c, 10) == 10 * 576 * 2 * 27
    assert fm.mla_decode_flops(c, 10) == 10 * 16 * (4 * 512 + 2 * 64) * 27
    assert fm.expert_flops(c) == 6 * 2048 * 1408
    assert fm.expert_bytes(c) == 3 * 2048 * 1408 * 2
    mla = (2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048)
    dense0 = 3 * 2048 * 10944
    moe = 2048 * 64 + 3 * 2048 * 1408 * 2
    head = 2 * 2048 * 102400
    token = 2 * (27 * mla + dense0 + 26 * moe) + head
    assert fm.token_flops(c) == token
    assert fm.decode_flops(c, 100) == token + fm.mla_decode_flops(c, 100)
    # a prompt attends causally and computes the head at one position
    per_key = 2 * 16 * (128 + 64 + 128) * 27
    assert fm.prefill_flops(c, 3) == 3 * (token - head) + 6 * per_key + head


# -- the readers on a synthetic trace and span ring ---------------------------

MS = 1_000_000
TRACE = (3.0, 4.0)                 # perf_counter seconds of the traced stretch
MODEL = json.loads(CONFIG.read_text())["model"]
PEAK, HBM = 197e12, 819e9


def read(name, run):
    return harness._module(METRICS / f"{name}.py").read(run)


def _op(a_ms, b_ms, scope, program, label="fusion"):
    return tr.Op(int(a_ms * MS), int(b_ms * MS), "packed_step", label,
                 program, scope and f"jit(packed_step)/while/body/{scope}/x")


def _trace(ops):
    return tr.Reduced(window_ns=(0, 1000 * MS), host_window_s=TRACE,
                      planes=1, busy_ns=0.0, ops=ops,
                      programs={7: [40 * MS, 4], 9: [30 * MS, 2]})


class _Ring:
    def __init__(self):
        self.recs, self.n = [], 0

    def add(self, name, t0_ms, t1_ms, parent=None, **attrs):
        self.n += 1
        rec = wall.Record(self.n, parent.id if parent else 0, name,
                          int(t0_ms * MS), int(t1_ms * MS), 1, attrs)
        self.recs.append(rec)
        return rec

    def tick(self, t0_ms, counts, *, admitted=0, prompt=None):
        t = self.add("sched.tick", t0_ms, t0_ms + 20, stepped=4,
                     admitted=admitted, compiled=False)
        if prompt is not None:
            p = self.add("engine.prefill", t0_ms + 1, t0_ms + 5, t)
            self.add("engine.routed", t0_ms + 4, t0_ms + 4, p,
                     counts=np.asarray(prompt))
        c = self.add("engine.consume", t0_ms + 15, t0_ms + 16, t)
        self.add("engine.routed", t0_ms + 15.5, t0_ms + 15.5, c,
                 counts=np.asarray(counts))
        return t


@pytest.fixture
def ring(monkeypatch):
    r = _Ring()
    monkeypatch.setattr(spans, "recorded", lambda: (r.recs, 0))
    return r


def _run(trace, ticks=()):
    return NS(trace=trace, window=NS(t_open=1.0, ticks=list(ticks)),
              model=MODEL, device_kind="TPU v5 lite")


def test_mla_decode_roofline_and_device_time():
    stack = "bitcast_dynamic-update-slice_fusion bf16[32,2048,{}]"
    ops = [_op(100, 101, "KERNEL_mla_decode", 7),
           _op(100.5, 102, "KERNEL_mla_decode", 7),     # overlaps: 2 ms in all
           # the rows' stack of latents and rope keys: no source op, counted
           _op(102, 103, "", 7, stack.format(512)),
           _op(103, 103.5, "", 7,
               "dynamic-slice_dynamic-update-slice_fusion bf16[32,2048,64]"),
           # unscoped, but not the stack: the in-place row write, a matmul
           _op(104, 105, "", 7, "fusion bf16[32,2048,512]"),
           _op(105, 106, "", 7, "fusion bf16[32,2048]"),
           _op(200, 201, "KERNEL_moe_experts", 7),
           _op(300, 310, "KERNEL_flash_attention", 9),
           _op(400, 420, "", 9, stack.format(512))]   # not in a packed step
    tick = Tick(3.1, 3.2, decode_kv=[1000, 2000])
    run = _run(_trace(ops), [tick, Tick(2.0, 2.1, decode_kv=[5])])
    least = max(576 * 2 * 27 * 3000 / HBM, 16 * 2176 * 27 * 3000 / PEAK)
    assert read("mla_decode_roofline", run) == pytest.approx(
        100 * least / 3.5e-3)
    # the packed step's runs: program 7, 40 ms over 4 runs
    assert read("moe_decode_device_ms", run) == pytest.approx(10.0)


def test_expert_readers_take_the_counts_from_the_spans(ring):
    held = np.zeros((26, 8), np.int64)
    step = held.copy()
    step[:, :3] = [5, 1, 0]               # 2 experts active in each layer
    prompt = held.copy()
    prompt[:, 0] = 100
    ring.tick(1100, step)                 # untraced, decode-only
    ring.tick(1200, 2 * step)             # untraced, decode-only
    ring.tick(1300, step, admitted=1, prompt=prompt)   # admits: not read
    ring.tick(3100, step)                 # traced
    ring.tick(3200, step, admitted=1, prompt=prompt)   # traced, admits
    ops = [_op(100, 104, "KERNEL_moe_experts", 7),
           _op(200, 230, "KERNEL_moe_experts", 9),    # a prompt's: not read
           _op(300, 301, "KERNEL_mla_decode", 7)]
    ticks = [Tick(3.1, 3.12, decode_kv=[10]),
             Tick(3.2, 3.22, admitted=1, prefill_lens=[64], decode_kv=[11])]
    run = _run(_trace(ops), ticks)
    # (6 per layer + 12 per layer) / 8 experts, over two ticks
    assert read("expert_tokens_per_step", run) == pytest.approx(
        (6 / 8 + 12 / 8) / 2)
    # two traced steps, 2 x 26 active experts each, 6 x 26 assignments each
    least = max(2 * 52 * 3 * 2048 * 1408 * 2 / HBM,
                2 * 156 * 6 * 2048 * 1408 / PEAK)
    assert read("expert_roofline", run) == pytest.approx(100 * least / 4e-3)
    work = (fm.prefill_flops(MODEL, 64) + fm.decode_flops(MODEL, 10)
            + fm.decode_flops(MODEL, 11)
            + (2 * 156 + 2600) * fm.expert_flops(MODEL))
    assert read("moe_model_mfu", run) == pytest.approx(100 * work / PEAK)


def test_nothing_to_read_without_the_spans_and_scopes(ring, monkeypatch):
    names = ("mla_decode_roofline", "expert_roofline", "expert_tokens_per_step",
             "moe_decode_device_ms", "moe_model_mfu")
    for name in names:
        assert read(name, _run(None)) is None
    # a program without latent attention or experts (olmo's, or this one's
    # parent): its ops carry other scopes and it records no counts
    ring.tick(1100, np.ones((26, 8)))
    plain = [_op(100, 104, "KERNEL_paged_attention", 7)]
    ticks = [Tick(3.1, 3.12, decode_kv=[10])]
    for name in ("mla_decode_roofline", "expert_roofline",
                 "moe_decode_device_ms"):
        assert read(name, _run(_trace(plain), ticks)) is None
    ring.recs.clear()
    ring.add("sched.tick", 1100, 1120, stepped=4, admitted=0, compiled=False)
    ops = [_op(100, 104, "KERNEL_moe_experts", 7),
           _op(300, 301, "KERNEL_mla_decode", 7)]
    for name in ("expert_tokens_per_step", "expert_roofline", "moe_model_mfu"):
        assert read(name, _run(_trace(ops), ticks)) is None
    monkeypatch.setattr(spans, "recorded", lambda: None)
    for name in ("expert_tokens_per_step", "expert_roofline", "moe_model_mfu"):
        assert read(name, _run(_trace(ops), ticks)) is None
