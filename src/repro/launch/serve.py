"""Serving entry point: batched requests through the CC-aware engine.

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --requests 16
    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --no-smoke \
        --cc                           # published widths: run it on a TPU

``--smoke`` (the default) serves the 128-wide reduced config that runs on
the CPU; ``--no-smoke`` serves the published config.  Decode is real while
the TransferGateway charges bridge-law costs to the virtual clock, so one
run reports both real tokens and the CC economics of the chosen policy.
"""

from __future__ import annotations

import argparse
import os
from pathlib import Path
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ARCH_IDS, ModelConfig, get_config, smoke_config
from repro.core.policy import SchedulingPolicy
from repro.models.model import Model, decode_step, prefill
from repro.serving.engine import Request, ServingEngine
from repro.serving.sampler import SamplingParams, sample
from repro.serving.scheduler import Scheduler

POLICIES = {p.value: p for p in SchedulingPolicy}

#: the checkout's root: the compile cache lives under it at a fixed path,
#: because the path is part of the cache's key
REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> None:
    """Keep JAX's persistent compile cache at one fixed directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and it
    stands; otherwise the cache goes to ``<checkout>/.jax_cache``.  Called
    from entry points only, never at import."""
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO_ROOT / ".jax_cache"))


def seeded_prompts(n: int, vocab_size: int, *, min_len: int, max_len: int,
                   seed: int) -> list[list[int]]:
    """``n`` prompts of token ids in [1, vocab), lengths uniform in
    [min_len, max_len], all drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(min_len, max_len + 1, size=n)
    return [rng.integers(1, vocab_size, size=int(s)).tolist() for s in lens]


def serve(cfg: ModelConfig, prompts: Sequence[Sequence[int]], *,
          max_batch: int = 8, max_len: int = 256, max_new_tokens: int = 16,
          temperature: float = 0.0,
          policy: Optional[SchedulingPolicy] = None, cc_on: bool = True,
          seed: int = 0) -> tuple[dict, list[Request]]:
    """Serve ``prompts`` through ``Model`` -> ``ServingEngine`` ->
    ``Scheduler`` and close the engine.

    Returns the scheduler's stats and the finished requests in submission
    order.  The engine's params are
    ``Model(cfg).init(jax.random.PRNGKey(seed))``."""
    engine = ServingEngine(Model(cfg), max_batch=max_batch, max_len=max_len,
                           policy=policy, cc_on=cc_on, seed=seed)
    sched = Scheduler(engine)
    sampling = SamplingParams(temperature=temperature,
                              max_new_tokens=max_new_tokens)
    for i, prompt in enumerate(prompts):
        sched.submit(Request(f"req-{i}", prompt=list(prompt),
                             sampling=sampling))
    try:
        stats = sched.run()
    finally:
        engine.close()
    stats["policy"] = engine.policy.value
    order = {f"req-{i}": i for i in range(len(prompts))}
    return stats, sorted(engine.finished, key=lambda r: order[r.request_id])


def greedy_reference(cfg: ModelConfig, params, prompts, *,
                     max_new_tokens: int, max_len: int
                     ) -> tuple[list[list[int]], bool]:
    """Straight-line greedy decode of ``prompts`` as one fixed batch: a
    jitted prefill of each prompt, the caches stacked row by row, then
    jitted ``decode_step`` calls over all rows at once — no packing, no
    bucketing, no gather or scatter.

    Its tokens equal the engine's where the engine stepped the same rows
    together.  At another batch width a TPU may round a bf16 logit
    differently and flip a near-tie, so a batch-1 loop is no reference for
    a batch-8 engine step.  Returns each prompt's tokens and whether every
    logit they were sampled from was finite."""
    pre = jax.jit(lambda p, t: prefill(p, cfg, {"tokens": t}, max_len)[:2])
    dec = jax.jit(lambda p, c, t, i: decode_step(p, cfg, c, t, i))
    greedy = SamplingParams()
    logits, caches = zip(*(pre(params, np.asarray([p], np.int32))
                           for p in prompts))

    def stack_rows(key):
        axis = 1 if cfg.scan_layers and key == "blocks" else 0
        return jax.tree.map(lambda *rows: jnp.concatenate(rows, axis),
                            *(c[key] for c in caches))

    cache = {key: stack_rows(key) for key in caches[0]}
    logits = jnp.concatenate(logits)
    index = np.asarray([len(p) for p in prompts], np.int32)
    finite = [jnp.isfinite(logits).all()]
    tokens = [sample(logits, None, greedy)]
    for step in range(max_new_tokens - 1):
        logits, cache = dec(params, cache, tokens[-1][:, None], index + step)
        finite.append(jnp.isfinite(logits).all())
        tokens.append(sample(logits, None, greedy))
    streams = np.stack(jax.device_get(tokens), axis=1)
    return streams.tolist(), bool(np.all(jax.device_get(finite)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="olmo-1b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced 128-wide config (default); --no-smoke "
                         "serves the published widths")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--policy", choices=list(POLICIES), default=None,
                    help="default: CC-aware selection")
    ap.add_argument("--cc", action="store_true", help="confidential mode")
    ap.add_argument("--temperature", type=float, default=0.8)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    prompts = seeded_prompts(args.requests, cfg.vocab_size,
                             min_len=8, max_len=8, seed=0)
    stats, finished = serve(
        cfg, prompts, max_batch=args.batch, max_len=256,
        max_new_tokens=args.max_new_tokens, temperature=args.temperature,
        policy=POLICIES[args.policy] if args.policy else None, cc_on=args.cc)
    print(f"arch={cfg.name} cc={'on' if args.cc else 'off'} "
          f"policy={stats['policy']} batch={args.batch}")
    print("--- serving stats ---")
    for k, v in stats.items():
        print(f"{k:18s} {v:.4f}" if isinstance(v, float) else f"{k:18s} {v}")
    tput = stats["total_tokens"] / max(stats["virtual_time_s"], 1e-9)
    print(f"{'virtual tok/s':18s} {tput:.0f}  (bridge-law costed)")
    sample_req = finished[0]
    print(f"sample request {sample_req.request_id}: "
          f"prompt={sample_req.prompt[:4]}... "
          f"-> {sample_req.output_tokens[:8]}...")


if __name__ == "__main__":
    main()
