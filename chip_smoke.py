"""Serve olmo-1b at its published widths on a TPU through the normal serving
path, and check the tokens against a plain greedy loop.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four one-chip replicas behind the router

One chip: 8 seeded prompts of 64-512 tokens go through ``launch.serve.serve``
(``Model`` -> ``ServingEngine`` -> ``Scheduler``, packed decode, CC on,
greedy, 32 new tokens each, ``max_batch=8``, ``max_len=1024``).  Every
request must finish with no preemption and a clean close, and its tokens
must equal a straight-line loop over the same seeded params (jitted
prefill, then jitted ``decode_step`` over the 8 prompts as one fixed
batch, as the engine steps them), whose logits must all be finite.

``--chips 4``: ``build_cluster`` puts four olmo-1b replicas on four chips
(prefix-affinity routing, CC on) and serves 16 greedy requests.  The
replicas' params must sit on four distinct devices, and each request's
tokens must equal those of a lone replica of the same seed on chip 0 that
serves the same prompts its cluster replica got, in the same order.

Exits non-zero, printing no result, when JAX finds no TPU.  The last line
of stdout is the JSON result; the lines before it are labelled readings of
this run (the virtual-clock ones say so), not benchmark metrics.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import jax

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
MAX_NEW_TOKENS = 32


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling programs (a load
    from the persistent cache counts as a compile), read from
    ``jax.monitoring``."""

    EVENTS = "/jax/core/compile/"

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith(self.EVENTS):
            self.seconds += duration


def phase(name: str, clock: CompileClock, fn):
    """Run ``fn`` and print its wall and compile seconds."""
    c0, t0 = clock.seconds, time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    compiled = clock.seconds - c0
    print(f"[{name}] wall_s={wall:.3f} compile_s={compiled:.3f} "
          f"wall_minus_compile_s={wall - compiled:.3f}")
    return out


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def one_chip(cfg, clock: CompileClock) -> None:
    from repro.launch.serve import greedy_reference, seeded_prompts, serve
    from repro.models.model import Model

    prompts = seeded_prompts(8, cfg.vocab_size, min_len=64, max_len=512,
                             seed=SEED)
    print(f"prompt lengths: {[len(p) for p in prompts]}")

    def run_serve():
        return serve(cfg, prompts, max_batch=8, max_len=1024,
                     max_new_tokens=MAX_NEW_TOKENS, temperature=0.0,
                     cc_on=True, seed=SEED)

    stats, finished = phase("serve, first run", clock, run_serve)
    gc.collect()
    print(f"engine policy={stats['policy']} steps={stats['steps']} "
          f"finished={stats['finished']} preemptions={stats['preemptions']} "
          f"closed_dirty={stats['closed_dirty']}")
    print(f"virtual clock (modelled, not measured): "
          f"virtual_time_s={stats['virtual_time_s']:.6f} "
          f"bridge_time_s={stats['bridge_time_s']:.6f} "
          f"compute_time_s={stats['compute_time_s']:.6f} "
          f"mean_ttft_s={stats['mean_ttft_s']:.6f}")
    check(stats["finished"] == len(prompts) == len(finished),
          f"{stats['finished']} of {len(prompts)} requests finished")
    check(stats["preemptions"] == 0, f"{stats['preemptions']} preemptions")
    check(not stats["closed_dirty"], "engine closed dirty")
    check(all(len(r.output_tokens) == MAX_NEW_TOKENS for r in finished),
          "a request stopped short of its token budget")

    warm_stats, warm = phase("serve, second run (same shapes)", clock,
                             run_serve)
    gc.collect()
    check(warm_stats["preemptions"] == 0,
          f"{warm_stats['preemptions']} preemptions on the second run")
    check([r.output_tokens for r in warm] == [r.output_tokens for r in finished],
          "second run's tokens differ from the first's")

    def run_reference():
        params = Model(cfg).init(jax.random.PRNGKey(SEED))
        out = greedy_reference(cfg, params, prompts,
                               max_new_tokens=MAX_NEW_TOKENS, max_len=1024)
        del params
        return out

    ref_tokens, finite = phase("straight-line greedy reference", clock,
                               run_reference)
    gc.collect()
    check(finite, "a logit of the reference loop is not finite")
    diverged = [(req.request_id, next(i for i, (a, b) in enumerate(
        zip(req.output_tokens, ref)) if a != b))
        for req, ref in zip(finished, ref_tokens) if req.output_tokens != ref]
    check(not diverged, f"{len(diverged)} of {len(finished)} requests "
          f"diverge from the reference (request, first token): {diverged}")
    print(f"token identity: {len(finished)} requests x {MAX_NEW_TOKENS} "
          f"tokens equal to the straight-line loop")


def four_chips(cfg, clock: CompileClock) -> None:
    import numpy as np

    from repro.cluster import RoutingPolicy, build_cluster
    from repro.cluster.replica import ReplicaConfig
    from repro.models.model import Model
    from repro.serving.engine import Request
    from repro.serving.sampler import SamplingParams

    check(len(jax.devices()) == 4, f"--chips 4 found {len(jax.devices())} "
          f"devices")
    model = Model(cfg)
    # n_pages: the bookkeeping page pool holds every prompt a replica gets,
    # so no request is preempted for pages
    rcfg = ReplicaConfig(max_batch=4, max_len=1024, n_pages=512)
    sampling = SamplingParams(temperature=0.0, max_new_tokens=MAX_NEW_TOKENS)
    # four sessions of four turns: each shares a 64-token system prefix, so
    # prefix affinity groups a session on one replica; tails take four
    # lengths so only four prompt shapes compile
    rng = np.random.default_rng(SEED)
    prefixes = [rng.integers(1, cfg.vocab_size, 64).tolist() for _ in range(4)]
    prompts = {f"s{s}t{t}": prefixes[s] + rng.integers(
        1, cfg.vocab_size, 32 * (t + 1)).tolist()
        for s in range(4) for t in range(4)}

    def run_cluster():
        cluster = build_cluster(model, n_replicas=4, partition_size=1,
                                routing=RoutingPolicy.PREFIX_AFFINITY,
                                cc_on=True, replica_cfg=rcfg, seed=SEED)
        homes = [next(iter(jax.tree.leaves(r.engine.params)[0].devices()))
                 for r in cluster.replicas]
        print(f"replica devices: {[str(d) for d in homes]}")
        check(len(set(homes)) == 4, "replicas share a device")
        for rid, prompt in prompts.items():
            check(cluster.submit(Request(rid, prompt=prompt,
                                         sampling=sampling)) is not None,
                  f"cluster rejected {rid}")
        stats = cluster.run()
        placed = {e["request"].request_id: (e["replica_id"],
                                            list(e["request"].output_tokens))
                  for e in cluster.request_log}
        preempted = sum(r.scheduler.preemptions for r in cluster.replicas)
        cluster.close()
        return stats, placed, preempted

    stats, placed, preempted = phase("cluster of 4 replicas", clock,
                                     run_cluster)
    gc.collect()
    print(f"cluster finished={stats['finished']} "
          f"affinity_hits={stats['affinity_hits']} preemptions={preempted} "
          f"placement={ {rid: home for rid, (home, _) in placed.items()} }")
    print(f"virtual clock (modelled, not measured): "
          f"makespan_s={stats['makespan_s']:.6f} "
          f"tokens_per_s={stats['tokens_per_s']:.1f}")
    check(stats["finished"] == len(prompts), f"{stats['finished']} of "
          f"{len(prompts)} requests finished")
    check(preempted == 0, f"{preempted} preemptions")

    for i in range(4):
        mine = [rid for rid, (home, _) in placed.items()
                if home == f"replica-{i}"]
        if not mine:
            continue

        def run_lone():
            lone = build_cluster(model, n_replicas=1, partition_size=1,
                                 cc_on=True, replica_cfg=rcfg, seed=SEED + i)
            reqs = [Request(rid, prompt=prompts[rid], sampling=sampling)
                    for rid in mine]
            for req in reqs:
                check(lone.submit(req) is not None,
                      f"lone rejected {req.request_id}")
            lone.run()
            lone.close()
            return {req.request_id: req.output_tokens for req in reqs}

        lone_tokens = phase(f"lone replica of seed {SEED + i} on chip 0",
                            clock, run_lone)
        gc.collect()
        for rid in mine:
            check(placed[rid][1] == lone_tokens[rid],
                  f"{rid} on replica-{i}: {placed[rid][1]} vs lone replica "
                  f"{lone_tokens[rid]}")
    print(f"token identity: {len(placed)} requests on 4 chips equal to lone "
          f"replicas on chip 0")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{devices[0].platform}")
    from repro.configs.base import get_config
    from repro.launch.serve import use_compile_cache

    use_compile_cache()
    clock = CompileClock()
    cfg = get_config("olmo-1b")
    print(f"device kind={devices[0].device_kind} count={len(devices)}")
    print(f"model {cfg.name}: {cfg.param_count()} parameters, "
          f"{cfg.n_layers} layers, d_model {cfg.d_model}")
    if args.chips == 4:
        four_chips(cfg, clock)
    else:
        one_chip(cfg, clock)
    for d in devices:
        mem = d.memory_stats() or {}
        print(f"{d}: peak_bytes_in_use={mem.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
