"""One run of one cell: build the engine as ``launch/serve.serve()`` does,
warm every shape the cell's traffic uses, pre-roll that traffic, measure
for ``seconds`` of wall time, then check what the timed path served against
the configuration's plain reference.

Everything that belongs to one cell is found by name under
``<root>/chipbench``: ``configs/<config>.json`` and its reference
``configs/<config>.py``, ``traffic/<traffic>.json``, ``cells/<cell>.json``
(the correctness limit and the readings it was set from) and
``metrics/<metric>.py`` for each metric ``BENCHMARK.json`` gives the cell.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import refcore, traffic as traffic_mod
from chipbench.compile_clock import CompileClock
from chipbench.loop import Driver

#: seconds of the window's end that a ``--trace 1`` run records
TRACE_SECONDS = 10.0


# -- the cell's files ------------------------------------------------------------

def _module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> SimpleNamespace:
    """Everything one cell needs, read from the files ``BENCHMARK.json``
    names."""
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    wl = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    config_path = root / conf["file"]
    base = root / "chipbench"
    return SimpleNamespace(
        name=name, chips=int(wl["chips"]),
        config=json.loads(config_path.read_text()),
        reference=_module(config_path.with_suffix(".py")),
        traffic=traffic_mod.load(base / "traffic" / f"{wl['traffic']}.json"),
        limits=json.loads((base / "cells" / f"{name}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        metrics_dir=base / "metrics")


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file: the registry
    entry of ``arch`` with every size the file states."""
    from repro.configs.base import get_config
    fields = {}
    for k, v in config["model"].items():
        if k in ("dtype", "logits_dtype"):
            v = jnp.dtype(v).type
        elif isinstance(v, list):
            v = tuple(v)
        fields[k] = v
    return dataclasses.replace(get_config(config["arch"]), **fields)


def engine_seed(seed: int) -> int:
    """The program's weight seed: 31 bits drawn from the run's seed, so
    seeds that differ above bit 31 still give different weights."""
    return int(np.random.SeedSequence(int(seed) % (1 << 63)
                                      ).generate_state(1)[0] & 0x7FFFFFFF)


# -- set-up ----------------------------------------------------------------------

def build(cell, seed: int):
    from repro.models.model import Model
    from repro.serving.engine import ServingEngine
    from repro.serving.scheduler import Scheduler
    cfg = model_config(cell.config)
    engine = ServingEngine(Model(cfg), max_batch=cell.traffic.max_batch,
                           max_len=cell.config["max_len"], cc_on=True,
                           seed=engine_seed(seed))
    return engine, Scheduler(engine)


def make_request(i: int, prompt: list, max_new: int):
    from repro.serving.engine import Request
    from repro.serving.sampler import SamplingParams
    return Request(f"r{i}", prompt=prompt,
                   sampling=SamplingParams(temperature=0.0,
                                           max_new_tokens=max_new))


def warm(sched, traffic, vocab: int) -> None:
    """Run every program the traffic will: a prefill of each ladder rung
    and the packed step at every width from ``max_batch`` down to one, as
    staggered requests finish one per step."""
    engine = sched.engine
    b = traffic.max_batch
    rng = np.random.default_rng(0)
    ladder = traffic.ladder
    n = max(b, len(ladder))
    for i in range(n):
        prompt = rng.integers(1, vocab, ladder[i % len(ladder)]).tolist()
        sched.submit(make_request(-1 - i, prompt, 2 + i))
    while engine.queue or engine.active:
        sched.tick()
    engine.finished.clear()


# -- one run ---------------------------------------------------------------------

class GcClock:
    """Python's garbage collections while it is open: how many of each
    generation, and the seconds they took."""

    def __init__(self):
        self.counts = [0, 0, 0]
        self.seconds = 0.0
        self.longest = 0.0
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            dt = time.perf_counter() - self._t
            self.seconds += dt
            self.longest = max(self.longest, dt)
            self.counts[info["generation"]] += 1
            self._t = None

    def snapshot(self) -> tuple:
        """(collections of each generation, seconds) so far; the longest
        is counted afresh from here."""
        self.longest = 0.0
        return list(self.counts), self.seconds

    def close(self) -> None:
        gc.callbacks.remove(self._on)


def stalls(ticks: list, t_open: float, t_close: float) -> dict:
    """Where the window's host time went between and inside ticks: the
    longest tick, the longest time between two ticks, and the ticks that
    took over three times the median, with their seconds."""
    inside = [t for t in ticks if t_open <= t.t0 and t.t1 <= t_close]
    if not inside:
        return {}
    dts = sorted(t.t1 - t.t0 for t in inside)
    slow = [d for d in dts if d > 3 * dts[len(dts) // 2]]
    between = [b.t0 - a.t1 for a, b in zip(inside, inside[1:])]
    return {"longest_tick_ms": 1e3 * dts[-1],
            "longest_between_ticks_ms": 1e3 * max(between, default=0.0),
            "ticks_over_3x_median": len(slow),
            "seconds_in_ticks_over_3x_median": sum(slow)}


def run_cell(root: Path, name: str, seed: int, seconds: float, trace: bool,
             *, t_process: float, control: bool = False) -> dict:
    """Serve one cell for ``seconds`` and return the result line's fields
    (and, with ``control``, the control's readings)."""
    cell = load_cell(root, name)
    clock = CompileClock()
    collections = GcClock()
    from repro.launch.serve import use_compile_cache
    use_compile_cache()
    device = jax.devices()[0]
    engine, sched = build(cell, seed)
    vocab = cell.config["model"]["vocab_size"]
    warm(sched, cell.traffic, vocab)
    requests = traffic_mod.Requests(cell.traffic, vocab, seed)
    driver = Driver(sched, requests, make_request)

    t_start = time.perf_counter()
    t_open = t_start + cell.traffic.preroll_s
    t_close = t_open + seconds
    marks = {}
    hooks = [(t_open, lambda: marks.update(
        open=time.perf_counter(), steps=len(engine.trace),
        compiles=clock.backend_compiles, compile_s=clock.seconds,
        preempt=sched.preemptions, gc=collections.snapshot()))]
    tracer = None
    if trace:
        from chipbench import trace_reduce
        tracer = trace_reduce.Tracer(Path(root) / ".chipbench")
        hooks.append((max(t_open, t_close - TRACE_SECONDS), tracer.start))
    driver.run(t_start, t_close, hooks=hooks)
    gc_longest = collections.longest
    gc_counts, gc_s = collections.snapshot()
    collections.close()
    if tracer is not None:
        tracer.stop()
    setup_s = t_open - t_process
    window = SimpleNamespace(
        recs=driver.recs, ticks=driver.ticks, t_open=t_open, t_close=t_close,
        seconds=t_close - t_open,
        packed=[s.packed for s in engine.trace[marks["steps"]:]],
        compiles=clock.backend_compiles - marks["compiles"],
        preemptions=sched.preemptions - marks["preempt"],
        rejected=sum(r.rejected for r in driver.recs
                     if t_open <= r.arrival < t_close))
    mem = device.memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))

    slot_of = {f"r{r.index}": r.slot for r in driver.recs}
    sample = pick_sample(engine.finished, cell.limits, seed, slot_of)
    del driver, sched, engine
    gc.collect()
    checks, readings = check(cell, sample, seed, control=control)

    reduced = tracer.reduce() if tracer is not None else None
    view = SimpleNamespace(
        setup_s=setup_s, window=window, trace=reduced,
        model=cell.config["model"], max_batch=cell.traffic.max_batch,
        device_kind=device.device_kind)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = _module(cell.metrics_dir / f"{m['name']}.py").read(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    arrived = [r for r in window.recs if t_open <= r.arrival < t_close]
    result = {
        "correct": verdict(checks),
        "attempted": len(arrived),
        "failed": window.rejected,
        "metrics": metrics,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak},
    }
    notes = {"compile_s_in_setup": marks["compile_s"],
             "preemptions": window.preemptions,
             "compiles_in_window": window.compiles,
             "requests_finished_in_window": sum(
                 1 for r in window.recs
                 if r.finished is not None and t_open <= r.finished <= t_close),
             "ticks_in_window": sum(1 for t in window.ticks
                                    if t_open <= t.t0 and t.t1 <= t_close),
             "gc_in_window": [a - b for a, b in zip(gc_counts, marks["gc"][0])],
             "gc_s_in_window": gc_s - marks["gc"][1],
             "gc_longest_s": gc_longest,
             **stalls(window.ticks, t_open, t_close)}
    if reduced is not None:
        result["device"].update(busy_s=reduced.busy_s,
                                window_s=reduced.window_s)
        result["breakdown"] = reduced.breakdown()
        notes["traced_ticks"] = len(reduced.ticks(window.ticks))
        notes["programs"] = {
            name: [t / 1e9, n] for name, (t, n) in sorted(
                reduced.modules.items(), key=lambda kv: -kv[1][0])[:12]}
    result["checks"] = checks
    return {"result": result, "notes": notes, "readings": readings}


# -- correctness -----------------------------------------------------------------

def pick_sample(finished: list, limits: dict, seed: int,
                slot_of: dict) -> list:
    """The longest finished request, then one request drawn from the seed
    of every slot that served any (``slot_of``: request id -> slot), then
    others drawn from the seed until ``sample_tokens`` served tokens or
    ``sample_requests`` requests."""
    if not finished:
        return []
    done = sorted(finished, key=lambda r: int(r.request_id[1:]))
    longest = max(done, key=lambda r: len(r.output_tokens))
    order = [done[j] for j in traffic_mod.seed_rng(seed, 3).permutation(
        len(done)) if done[j] is not longest]
    sample = [longest]
    slots = {slot_of.get(longest.request_id)}
    for r in order:
        slot = slot_of.get(r.request_id)
        if slot is not None and slot not in slots:
            sample.append(r)
            slots.add(slot)
    tokens = sum(len(r.output_tokens) for r in sample)
    for r in order:
        if (tokens >= limits["sample_tokens"]
                or len(sample) >= limits["sample_requests"]):
            break
        if not any(r is s for s in sample):
            sample.append(r)
            tokens += len(r.output_tokens)
    return [(list(r.prompt), list(r.output_tokens),
             r.sampling.max_new_tokens) for r in sample]


def reference_gaps(cell, sample: list, seed: int, *, control: bool):
    """Run the plain reference over each sampled prompt with its served
    tokens.  Returns, per request, the gap of each served token below the
    reference's best logit at the position that produced it, and with
    ``control`` the same for the token the fp8 control puts first."""
    c = cell.config["model"]
    ref = cell.reference
    length = cell.config["max_len"]
    params = jax.jit(lambda k: ref.init(k, c))(
        jax.random.PRNGKey(engine_seed(seed)))
    exact, fp8 = refcore.Matmul("exact"), refcore.Matmul("fp8")

    @jax.jit
    def gaps(params, tokens, nxt):
        logits = ref.forward(params, tokens, c, exact)
        served = refcore.token_gaps(logits, nxt)
        if not control:
            return served, served
        low = ref.forward(params, tokens, c, fp8)
        return served, refcore.token_gaps(logits, low.argmax(-1))

    out = []
    for prompt, served, _ in sample:
        seq = np.zeros(length + 1, np.int32)
        seq[:len(prompt) + len(served)] = prompt + served
        g, g_low = jax.device_get(gaps(params, jnp.asarray(seq[:-1]),
                                       jnp.asarray(seq[1:])))
        at = slice(len(prompt) - 1, len(prompt) - 1 + len(served))
        out.append((g[at], g_low[at]))
    del params
    return out


def verdict(checks: dict) -> bool:
    """Correct where every number compared is within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def _widest(gaps) -> float:
    widest = max(float(np.max(g)) for g in gaps)
    return widest if np.isfinite(widest) else float("inf")


def check(cell, sample: list, seed: int, *, control: bool = False):
    """The numbers compared, each with its limit, and the readings.  With
    ``control`` the fp8 control's tokens go through the same comparison in
    the program's place: ``control_correct`` is its verdict."""
    limits = cell.limits
    short = sum(len(s) < m for _, s, m in sample)
    checks = {"short_streams": {"value": short, "limit": 0},
              "logit_gap": {"value": float("inf"),
                            "limit": limits["max_logit_gap"]}}
    readings = {"sampled_requests": len(sample)}
    if sample:
        per_req = reference_gaps(cell, sample, seed, control=control)
        checks["logit_gap"]["value"] = _widest(g for g, _ in per_req)
        readings.update(logit_gap=checks["logit_gap"]["value"],
                        served_tokens=int(sum(len(g) for g, _ in per_req)))
        if control:
            low = _widest(gl for _, gl in per_req)
            readings.update(control_logit_gap=low, control_correct=verdict(
                dict(checks, logit_gap=dict(checks["logit_gap"], value=low))))
    return checks, readings
