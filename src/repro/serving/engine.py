"""Continuous-batching serving engine with CC-aware scheduling policies.

The engine is real: requests, slot allocation, one-shot prefill, batched
decode via the model's decode_step, per-slot sampling, straggler handling.
Every host<->device crossing goes through the TransferGateway, which (a)
executes the real JAX transfer and (b) charges the bridge-law cost of that
crossing to the engine's virtual clock, tagged with its op class — so a run
produces both correct tokens AND a crossing trace that the benchmarks replay
under each scheduling policy (core/simulator prices the pipeline shapes).

Policy structure per decode step (paper §5):
  SYNC_DRAIN    prep (batched, REGISTERED staging) -> forward -> sample ->
                one small D2H drain -> continue.  Drained pattern: every
                crossing sees an idle channel and warm staging.
  ASYNC_OVERLAP vLLM default: drain issued "non-blocking" + per-step fresh
                staging for the scatter/sampling-index uploads.  Under CC
                the gateway blocks anyway (L2) and fresh staging pays the
                bounce-buffer toll (L3): the measured 44x op class.
  WORKER_DRAIN  v10c: the drain is priced as a worker-channel crossing
                (``worker_drain``) and runs inline on the engine's thread.
                Input crossings return to warm staging.

Sampling runs inside the packed step, which keeps each slot's last token
on the device.  Under SYNC_DRAIN and WORKER_DRAIN a step in which no row
can end (no row reaches its length and none has a stop token) is priced
when it is dispatched and drained in the next tick, after the next step
is dispatched: one step stays in flight.  A step that can end a row
drains in its own tick.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.bridge_opt import CrossingCoalescer, StagingArena
from repro.core.bridge import BridgeModel
from repro.core.channels import VirtualClock
from repro.core.compute import ComputeModel
from repro.core.gateway import TransferGateway, device_put, to_host
from repro.core.policy import RuntimeDefaults, SchedulingPolicy, cc_aware_defaults
from repro.obs import Observatory, wall
from repro.trace import opclasses as oc
from repro.models.model import Model, prefill
from .kv_cache import RaggedBatch
from .overlap import OverlapScheduler
from .sampler import SamplingParams, sample


#: ``op_class`` of the step inputs the engine uploads itself, outside the
#: gateway and its tape (an attr of their ``bridge.h2d`` wall-clock spans)
DECODE_INPUT = "decode_input"


@dataclass
class Request:
    request_id: str
    prompt: list
    sampling: SamplingParams = field(default_factory=SamplingParams)
    state: str = "queued"             # queued|running|finished|preempted
    output_tokens: list = field(default_factory=list)
    slot: int = -1
    index: int = 0                    # current sequence length in cache
    enqueue_t: float = 0.0
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    decode_steps: int = 0
    restarts: int = 0                 # straggler/preemption requeues
    #: prompt tokens whose prefill compute is already accounted elsewhere —
    #: a restored warm prefix, or an admission layer (cluster Replica) that
    #: prices prompt processing itself.  The engine charges compute only
    #: for the cold tail.
    warm_tokens: int = 0


@dataclass
class StepTrace:
    """One decode step's crossing profile (replayed by benchmarks)."""
    step: int
    active: int
    prep_crossings: int
    prep_bytes: int
    drain_bytes: int
    policy: str
    virtual_t: float
    #: slots that sat this step out because their KV restore pipeline was
    #: still draining (slot-masked decode; 0 while no restore is in flight)
    deferred: int = 0
    #: rows the packed forward decoded (DESIGN.md §10): equal to ``active``
    #: on every step
    packed: int = 0


@dataclass
class _Flight:
    """A dispatched packed step whose drain is priced and whose tokens have
    not reached the host yet."""
    #: the rows' slots, in packed row order
    ready: list
    #: per row: the step ends it by length (decided at dispatch)
    ends: list
    width: int
    #: the program's drain array, on the device
    drain: Any
    #: the step's record; ``virtual_t`` is when its drain was priced
    trace: StepTrace


def _and_routed(logits, caches, routed) -> tuple:
    """A serving program's outputs: (logits, caches), and in a model with
    experts its (MoE layers, experts held) assignment counts after them."""
    return (logits, caches) if routed is None else (logits, caches, routed)


@functools.partial(jax.jit, static_argnums=(0, 3))
def prefill_logits_cache(cfg, params, tokens, max_len: int):
    """One compiled prompt pass: (last-position logits, max_len cache), and
    the prompt's MoE assignment counts in a model with experts.

    Shared by every engine: jit keys on (cfg, max_len, prompt shape,
    device), so each prompt length compiles once per process.  The next
    cache index is the prompt length."""
    logits, caches, _, routed = prefill(params, cfg, {"tokens": tokens},
                                        max_len, routed=True)
    return _and_routed(logits, caches, routed)


def packed_step(model: Model, params, caches, tokens, index, slots):
    """Packed ragged decode (DESIGN.md §10) on the resident cache: row i
    decodes slot ``slots[i]`` at the packed width, writing its new entries
    in place where the slot resides and attending over them there.
    Returns (logits, caches), and in a model with experts the assignment
    counts of every executed row."""
    return _and_routed(*model.decode_step(params, caches, tokens, index,
                                          slots, routed=True))


def packed_step_of(model: Model, device: Optional[jax.Device] = None):
    """``packed_step`` bound to ``model``, with sampling inside, behind one
    upload of its inputs.

    The returned callable takes the resident last-token vector (int32
    ``[max_batch]``), the sampling key, the step's host int32 inputs
    (tokens ``[w, 1]``, positions and slots ``[w]``) and the sampling
    parameters.  It stacks the inputs into one ``[3, w]`` buffer, puts that
    on ``device`` in one transfer, and runs the program (its ``program``
    attribute).  The program takes a row's input token from the vector by
    slot where the host's token is negative, steps, splits the key, samples
    every row at the executed width, writes the sampled tokens into the
    vector by slot, and returns (logits, caches, drain, vector, key):
    ``drain`` is the one int32 array the host copies, the sampled tokens
    then, in a model with experts, the assignment counts.  The program is
    jitted under the name ``packed_step``, so a profiler trace shows it so,
    with the sampling parameters static; the resident cache and the vector
    (arguments 1 and 2) are donated: the step updates them in place."""
    def program(params, caches, last, key, inputs, sampling):
        slots = inputs[2]
        tokens = jnp.where(inputs[0] < 0, last[slots], inputs[0])
        logits, caches, *routed = packed_step(
            model, params, caches, tokens[:, None], inputs[1], slots)
        key, sub = jax.random.split(key)
        sampled = sample(logits, sub, sampling)
        # pad rows repeat the first row's slot, and a draw at temperature
        # differs by row: every write to that slot carries the first row's
        last = last.at[slots].set(jnp.where(slots == slots[0], sampled[0],
                                            sampled))
        return logits, caches, _with_routed(sampled, routed), last, key
    program.__name__ = program.__qualname__ = "packed_step"
    program = jax.jit(program, static_argnums=(5,), donate_argnums=(1, 2))

    def step(params, caches, last, key, tokens, index, slots,
             sampling: SamplingParams = SamplingParams()):
        inputs = np.stack([np.asarray(tokens, np.int32).reshape(-1),
                           np.asarray(index, np.int32),
                           np.asarray(slots, np.int32)])
        return program(params, caches, last, key,
                       device_put(inputs, device, DECODE_INPUT), sampling)
    step.program = program
    return step


def _with_routed(tokens: jax.Array, routed: list) -> jax.Array:
    """The sampled tokens with a program's MoE assignment counts (``routed``:
    none, or the one array) after them, so that the one drain carries
    both."""
    if not routed:
        return tokens
    return jnp.concatenate([tokens, routed[0].reshape(-1).astype(tokens.dtype)])


class ServingEngine:
    def __init__(self, model: Model, *, max_batch: int = 8, max_len: int = 256,
                 gateway: Optional[TransferGateway] = None,
                 policy: Optional[SchedulingPolicy] = None,
                 cc_on: bool = False,
                 bridge: Optional[BridgeModel] = None,
                 defaults: Optional[RuntimeDefaults] = None,
                 compute_model: Optional[ComputeModel] = None,
                 obs: Optional[Observatory] = None,
                 seed: int = 0):
        from repro.core.bridge import TPU_V5E
        self.model = model
        self.cfg = model.cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.bridge = bridge or BridgeModel(TPU_V5E, cc_on=cc_on)
        self.defaults = defaults or cc_aware_defaults(self.bridge.cc_on)
        self.policy = policy or self.defaults.scheduling
        if gateway is None:
            # bridge_opt: staging becomes a budgeted arena when defaults ask
            arena = (StagingArena(self.defaults.staging_arena_bytes)
                     if self.defaults.staging_arena_bytes else None)
            gateway = TransferGateway(
                self.bridge, self.defaults,
                pool_workers=self.defaults.loader_pool_workers or 1,
                arena=arena)
        self.gateway = gateway
        #: bridge_opt: sub-threshold crossings queue here and flush fused —
        #: replaces both the fresh-per-step async path and eager batching.
        #: Under WORKER_DRAIN the composition applies: the worker takes the
        #: fused D2H flushes off the engine clock (ROADMAP item).
        self.coalescer = (CrossingCoalescer(
            self.gateway,
            worker_flush=self.policy is SchedulingPolicy.WORKER_DRAIN)
            if self.defaults.coalesce_small_crossings else None)
        self.clock: VirtualClock = self.gateway.clock
        #: compute-charged clock (DESIGN.md §7): per-step prefill/decode
        #: compute priced by the roofline and charged like any interval.
        #: `compute_model` lets benchmarks price a paper-scale config while
        #: executing the smoke model (the crossing side already does this).
        self.compute = compute_model or (
            ComputeModel(self.cfg, self.bridge)
            if self.defaults.charge_compute else None)
        #: restore-aware scheduling: barrier is law, preference is a flag
        self.overlap = OverlapScheduler(
            self.clock, self.gateway.pool,
            prefer_overlap=self.defaults.overlap_scheduler)
        #: observatory (DESIGN.md §9): metric registry + request spans, fed
        #: from the gateway's record stream and the lifecycle points below.
        #: Passive — it never reads or moves the clock, so tapes and token
        #: streams are identical with it on or off.  A caller-owned bundle
        #: (the cluster Replica passes its labeled one) wins over defaults.
        self.obs = obs if obs is not None else (
            Observatory() if self.defaults.observability else None)
        if self.obs is not None:
            self.obs.attach_gateway(self.gateway)
        #: attrs of this engine's root wall-clock span (``sched.tick``):
        #: a labelled observatory's replica
        self.span_attrs = ({"replica": self.obs.labels["replica"]}
                           if self.obs is not None
                           and "replica" in self.obs.labels else {})

        #: params and cache are built on the default device, so one seed
        #: gives the same bits everywhere, then committed to the gateway's
        #: device: every jitted step runs on that chip (one replica per chip)
        self.device = self.gateway.device
        #: ``last_tokens``: each slot's last sampled token, written by the
        #: packed step and read by the next, so it never waits on the host
        self.params, self.caches, self.last_tokens = jax.device_put(
            (model.init(jax.random.PRNGKey(seed)),
             model.init_cache(max_batch, max_len),
             np.zeros(max_batch, np.int32)), self.device)
        self.key = jax.random.PRNGKey(seed + 1)

        self.free_slots = list(range(max_batch))
        self.queue: list[Request] = []
        self.active: dict[int, Request] = {}
        self.finished: list[Request] = []
        self.trace: list[StepTrace] = []
        self.step_count = 0
        #: shapes this engine has already executed; a step that meets a new
        #: one (prompt length, packed width) compiles, and sets
        #: ``step_compiled`` so the scheduler does not time it as a straggler
        self._seen_shapes: set = set()
        self.step_compiled = False
        #: requests the last step admitted
        self.step_admitted = 0
        # packed ragged decode: jit caches one trace per packed width;
        # `_bucket` pads widths to powers of two so the trace count stays
        # O(log max_batch) even when the ready set raggedly shrinks slot by
        # slot as requests finish.
        self._packed_program = packed_step_of(model, self.device)
        #: packed steps run on the donated resident cache (``stats()``)
        self.resident_decode_steps = 0
        #: the packed step dispatched and priced, not yet drained
        self._flight: Optional[_Flight] = None
        #: packed steps dispatched while their predecessor was in flight
        #: (``stats()``)
        self.lookahead_steps = 0
        #: running total of the MoE programs' assignments to each (MoE
        #: layer, expert held here), over prefills and packed steps
        #: (``stats()``; ``None`` without experts)
        self.routed_assignments: Optional[np.ndarray] = None

        # worker x coalescer composition: the drains queue and the worker's
        # seat becomes a secure channel the fused flushes serialize on —
        # prewarm the pool so the first flush never pays context creation
        # on the serving path (§6.1 discipline)
        if (self.policy is SchedulingPolicy.WORKER_DRAIN
                and self.coalescer is not None):
            self.gateway.pool.prewarm()

    def _note_shape(self, key: tuple) -> None:
        if key not in self._seen_shapes:
            self._seen_shapes.add(key)
            self.step_compiled = True

    def _bucket(self, n: int) -> int:
        """Smallest power-of-two >= n, capped at max_batch: the executed
        width of a packed step.  Pad rows duplicate a real slot, so the
        duplicate cache writes carry identical values (deterministic) —
        accounting (crossings, compute charge, drain) always prices the
        REAL packed size, never the bucket."""
        b = 1
        while b < n:
            b <<= 1
        return min(b, self.max_batch)

    def close(self):
        self.settle()
        if self.coalescer is not None:
            self.coalescer.barrier()

    # -- request lifecycle -------------------------------------------------------------

    def submit(self, request: Request) -> None:
        request.enqueue_t = self.clock.now
        request.state = "queued"
        self.queue.append(request)
        if self.obs is not None:
            # last-wins: an admission layer that knows the true arrival
            # time (cluster Replica) re-stamps the span after this
            self.obs.spans.on_enqueue(request.request_id, request.enqueue_t)

    def mark_restore(self, request_id: str, done_t: float) -> None:
        """Register that `request_id`'s KV restore lands at virtual `done_t`
        (the offload layer's pipelined restore completion).  The engine will
        barrier before the request's KV is first read, and — with the
        overlap preference on — fill the drain window with other decode
        work before admitting it."""
        self.overlap.note_restore(request_id, done_t)

    def restore_barrier(self, request_id: str) -> float:
        """PipeLLM correctness edge: block until the restore pipeline for
        `request_id` has drained.  No-op if nothing is pending or it already
        landed.  Returns virtual seconds waited."""
        return self.overlap.restore_barrier(request_id)

    def _admit(self) -> None:
        # Restore-aware admission: a request whose restore pipeline is still
        # draining defers while other work can fill the window with decode
        # compute (the §5.5 overlap, made real by the compute-charged
        # clock).  Deferral is only ever a preference — if nothing else
        # could make progress the request admits and the barrier waits.
        # deferral granularity is one engine step: admitting later means one
        # extra serialized step at the tail, so a window must be worth at
        # least that much before deferring into it — priced off the READY
        # set (the slots that would actually step now), with per-slot KV
        # prefixes, the same terms the step itself will charge.  A resident
        # slot whose own restore is still draining does not step and must
        # not inflate the admission price; with nothing ready the next step
        # pays a barrier, not a forward, and the cost is honestly zero.
        if self.compute is not None:
            with wall.span("account", what="admission_price"):
                step_cost = self.compute.decode_step_s(self._ready_lens())
        else:
            step_cost = 0.0
        i = 0
        admitted = False
        while self.free_slots and i < len(self.queue):
            req = self.queue[i]
            others = bool(self.active) or admitted or i + 1 < len(self.queue)
            if others and self.overlap.should_defer(req.request_id,
                                                    step_cost_s=step_cost):
                self.overlap.record_deferral(req.request_id)
                i += 1
                continue
            self.queue.pop(i)
            slot = self.free_slots.pop()
            with wall.span("engine.prefill", req=req.request_id,
                           prompt_len=len(req.prompt)):
                self._prefill_into_slot(req, slot)
            self.step_admitted += 1
            admitted = True

    def _ready_lens(self) -> list:
        """Per-slot KV lengths of the resident slots that would step now.

        The masked-aware admission price: with restores in flight, only the
        ready slots' lengths count; otherwise every resident slot steps.
        Empty when nothing would step — the phantom-charge fix makes the
        resulting price exactly zero."""
        if not self.active:
            return []
        if self.overlap.pending:
            key_of = {s: r.request_id for s, r in self.active.items()}
            mask = self.overlap.ready_mask(key_of)
            return [float(r.index) for s, r in self.active.items() if mask[s]]
        return [float(r.index) for r in self.active.values()]

    def _prefill_into_slot(self, req: Request, slot: int) -> None:
        if self.obs is not None:
            self.obs.spans.on_admit(req.request_id, self.clock.now)
        # first read of restored KV happens here: the barrier is the law
        with wall.span("account", what="restore_barrier"):
            waited = self.overlap.restore_barrier(req.request_id)
            if waited:
                if self.coalescer is not None:
                    self.coalescer.poll()   # the barrier wait moved the clock
                if self.obs is not None:
                    self.obs.spans.on_restore_wait(req.request_id, waited)
        prompt = np.asarray(req.prompt, np.int32)[None]     # (1, P)
        # prompt upload crosses the bridge (registered: steady-state serving
        # reuses the prompt staging buffer; coalesced when bridge_opt is on)
        # and the prompt program reads what it put
        if self.coalescer is not None:
            prompt_dev = self.coalescer.h2d(prompt, op_class=oc.PROMPT_H2D)
        else:
            prompt_dev = self.gateway.h2d(prompt, op_class=oc.PROMPT_H2D)
        self._note_shape(("prefill", prompt.shape[1]))
        logits, pre_cache, *routed = prefill_logits_cache(
            self.cfg, self.params, prompt_dev, self.max_len)
        idx0 = prompt.shape[1]
        # prompt processing is device compute, charged like any interval;
        # warm (restored / externally-priced) tokens skip the forward
        cold = max(0, len(req.prompt) - req.warm_tokens)
        if self.compute is not None and cold:
            with wall.span("account", what="prefill_charge"):
                charge = self.compute.prefill_charge(cold)
                self.gateway.charge_compute(
                    charge.seconds, op_class=oc.PREFILL_COMPUTE,
                    bound=charge.bound)
                if self.coalescer is not None:
                    self.coalescer.poll()   # prefill compute moved the clock
                # TP prefill allreduces over the prompt's activations (the
                # per-token payload is the same shape as a decode batch row)
                self._charge_allreduce(cold)
        with wall.span("engine.insert"):
            self._insert_slot_cache(pre_cache, slot)
        with wall.span("engine.sample"):
            self.key, sk = jax.random.split(self.key)
            first = sample(logits, sk, req.sampling)
        first = _with_routed(first, routed)
        if self.coalescer is not None:
            first_host = self.coalescer.d2h(first, op_class=oc.SAMPLE_D2H)
        else:
            first_host = self.gateway.d2h(first, op_class=oc.SAMPLE_D2H)
        first_host = self._take_routed(np.asarray(first_host), 1, 1)
        tok = int(first_host[0])
        req.output_tokens.append(tok)
        req.first_token_t = self.clock.now
        if self.obs is not None:
            self.obs.spans.on_token(req.request_id, req.first_token_t)
        req.state = "running"
        req.slot = slot
        req.index = idx0
        req.decode_steps = 0
        self.active[slot] = req

    def _insert_slot_cache(self, pre_cache, slot: int) -> None:
        def merge(full, one, stacked: bool):
            if stacked:
                return full.at[:, slot].set(one[:, 0].astype(full.dtype))
            return full.at[slot].set(one[0].astype(full.dtype))

        def walk(full_tree, one_tree, stacked: bool):
            if isinstance(full_tree, dict):
                return {k: walk(full_tree[k], one_tree[k], stacked)
                        for k in full_tree}
            if isinstance(full_tree, list):
                return [walk(f, o, stacked) for f, o in zip(full_tree, one_tree)]
            return merge(full_tree, one_tree, stacked)

        new = {}
        for key, sub in self.caches.items():
            stacked = self.cfg.scan_layers and key == "blocks"
            new[key] = walk(sub, pre_cache[key], stacked)
        self.caches = new

    def _release(self, req: Request, *, state: str = "finished") -> None:
        """Free ``req``'s slot, finished or requeued.  A release from
        outside the step lands the step in flight first, so the request
        leaves with every token it decoded."""
        self.settle()
        if req.slot >= 0:
            self.active.pop(req.slot, None)
            self.free_slots.append(req.slot)
            req.slot = -1
        req.state = state
        if state == "finished":
            req.finish_t = self.clock.now
            self.finished.append(req)
            if self.obs is not None:
                self.obs.spans.on_finish(req.request_id, req.finish_t)
        else:
            req.restarts += 1
            req.output_tokens.clear()
            self.queue.append(req)
            if self.obs is not None:
                self.obs.spans.on_preempt(req.request_id, self.clock.now)

    # -- degradation ladder (resilience, DESIGN.md §11) --------------------------------

    def _ladder(self):
        """The fault injector's degradation ladder, when one is attached to
        the gateway (duck-typed — the engine never imports resilience)."""
        faults = getattr(self.gateway, "faults", None)
        return faults.ladder if faults is not None else None

    def _degraded_tags(self) -> tuple:
        """DEGRADED on every compute charge while the ladder sits above
        level 0 — the tape shows exactly which intervals ran degraded."""
        ladder = self._ladder()
        if ladder is not None and ladder.level > 0:
            return (oc.DEGRADED,)
        return ()

    def _sync_ladder(self) -> None:
        """Per-step ladder bookkeeping: recovery hysteresis on the virtual
        clock, and the coalescer-bypass rung applied/released (entering
        bypass barrier-flushes both queues so nothing is stranded)."""
        ladder = self._ladder()
        if ladder is None:
            return
        with wall.span("account", what="ladder"):
            ladder.maybe_recover(self.clock.now)
            if (self.coalescer is not None
                    and self.coalescer.bypass != ladder.coalescer_bypassed):
                self.coalescer.set_bypass(ladder.coalescer_bypassed)

    # -- tensor-parallel allreduce (DESIGN.md §12) --------------------------------------

    def _charge_allreduce(self, batch: int) -> None:
        """Charge one step's TP ring allreduce over the tenant fabric.

        Only a TP>1 compute model owes one (a single-device replica has
        nothing to reduce — the record never appears, so TP=1 tapes are
        byte-identical to pre-TP tapes).  The bytes ride ``gateway.p2p``:
        kind="p2p", priced at the fabric rate (or the TCP fallback for a
        stale/unattested tenant), never the bridge.  Execution is untouched
        — the smoke model still runs unsharded — which is exactly why TP
        token streams are byte-identical to TP=1.
        """
        if self.compute is None or self.compute.tp_degree == 1:
            return
        nbytes = self.compute.allreduce_bytes(batch)
        if nbytes == 0:
            return
        # per-device clock skew (DESIGN.md §13): the ring closes when the
        # slowest device arrives, so the skew spread rides the p2p charge as
        # extra seconds — zero skew (the default) prices exactly as before
        self.gateway.p2p(nbytes, op_class=oc.P2P_ALLREDUCE,
                         extra_s=self.compute.allreduce_skew_s())
        if self.coalescer is not None:
            self.coalescer.poll()   # the allreduce moved the clock

    # -- the decode step under each policy ------------------------------------------------

    def _ready_slots(self, slots: list) -> tuple[list, list]:
        """Split this step's slots into (ready, deferred) by restore state.

        Slot-masked decode (DESIGN.md §8): each slot's read set is its own
        request's KV, so readiness is per slot — a slot whose restore
        pipeline is still draining sits the step out (stays resident,
        rejoins once the pipeline lands) instead of barriering the whole
        batch.  The barrier law survives intact in two places: a ready slot
        with a *landed* restore resolves it here (a barrier no-op — the
        overlap win), and when no slot is ready the nearest pipeline is paid
        as a real barrier so the batch always makes progress.  With no
        restores in flight every slot is ready.
        """
        if not self.overlap.pending:
            return list(slots), []
        key_of = {s: self.active[s].request_id for s in slots}
        mask = self.overlap.ready_mask(key_of)
        ready = [s for s in slots if mask[s]]
        deferred = [s for s in slots if not mask[s]]
        if not ready:
            # law, not preference: nothing can step — pay the nearest
            # pipeline's barrier, then re-ask (others may have landed too)
            nearest = min(
                deferred, key=lambda s: self.overlap.pending_done_t(key_of[s]))
            waited = self.overlap.restore_barrier(key_of[nearest])
            if waited:
                if self.coalescer is not None:
                    self.coalescer.poll()   # the barrier wait moved the clock
                if self.obs is not None:
                    self.obs.spans.on_restore_wait(key_of[nearest], waited)
            mask = self.overlap.ready_mask(key_of)
            ready = [s for s in slots if mask[s]]
            deferred = [s for s in slots if not mask[s]]
        for s in ready:
            # first KV read of a landed restore: resolve it (barrier no-op)
            self.overlap.restore_barrier(key_of[s])
        for s in deferred:
            self.overlap.record_slot_deferral(key_of[s])
        if deferred and self.coalescer is not None:
            # deferral masks *slots*, never flushes: crossings queued by
            # deferred slots keep aging toward deadline_s on this clock
            self.coalescer.poll(source="deferral")
        return ready, deferred

    def step(self) -> int:
        """One engine iteration; returns number of active sequences stepped.

        Slots whose restore pipelines are still draining are masked out of
        the step (``_ready_slots``), and the step executes over a packed
        ragged batch of exactly the ready slots (``_step_packed``): prep
        crossings, the forward, the compute charge and the drain are all
        sized to the packed set, while deferred slots stay resident and
        rejoin next step.

        Where a step is in flight and the next step can end a row, a
        running one or one an admission adds, this iteration only lands the
        step in flight (and returns its rows): the next step then drains in
        its own iteration, and no iteration delivers two steps' tokens.
        """
        self.step_compiled = False
        self.step_admitted = 0
        flight = self._flight
        if flight is not None and (
                any(self._may_end(s, req, flight.ready)
                    for s, req in self.active.items())
                or (self.free_slots
                    and any(map(self._may_end_first, self.queue)))):
            self.settle()
            return len(flight.ready)
        self._sync_ladder()
        with wall.span("engine.admit"):
            self._admit()
        if not self.active:
            return 0
        self.step_count += 1
        slots = sorted(self.active)
        with wall.span("account", what="ready_slots"):
            ready, deferred = self._ready_slots(slots)
        return self._step_packed(ready, deferred)

    def _step_packed(self, ready: list, deferred: list) -> int:
        """Packed ragged decode step (DESIGN.md §10).

        The step executes over exactly the ready slots: a ``RaggedBatch``
        names the packed rows and their per-slot KV lengths, and the forward
        decodes and samples them at the packed width on the donated resident
        cache, writing each row's new entries where its slot resides.  Prep
        crossings, the compute charge (``DECODE_PACKED``, priced per slot KV
        length) and the drain all cover the packed set and nothing else, so
        a half-empty engine stops shipping ``max_batch``-shaped bytes across
        the bridge and billing phantom lanes.  Rows are batch-independent
        and consume in ascending slot order, so under greedy decode the
        tokens do not depend on the width.

        The step is dispatched before the step in flight is drained.  Where
        no row can end in it and the drain is the gateway's own (no
        coalescer, not ``ASYNC_OVERLAP``), its drain is priced now and it
        stays in flight until the next step is dispatched; otherwise it
        drains in this tick.
        """
        batch = RaggedBatch.from_slots(
            [(s, self.active[s].index) for s in ready])
        n = batch.size
        # a row whose last token the step in flight sampled reads it on the
        # device, from the resident vector (host token -1)
        behind = set(self._flight.ready) if self._flight is not None else ()
        tokens = np.asarray(
            [[-1 if s in behind else self.active[s].output_tokens[-1]]
             for s in ready], np.int32)
        index = np.asarray(batch.kv_lens, np.int32)

        # --- input prep crossings: sized to the packed set, never max_batch
        small_inputs = [tokens, index] + [
            np.zeros((n,), np.int32) for _ in range(4)]
        self._emit_prep(small_inputs)

        # --- packed forward at the bucketed width, or at the fixed full
        # width while the ladder forces its last rung.  Pad rows (if any)
        # duplicate the first packed slot: the duplicate cache writes
        # carry identical values, so the width is invisible to state —
        # and ALL accounting above/below prices the real size n.
        ladder = self._ladder()
        width = (self.max_batch
                 if ladder is not None and ladder.dense_step_forced
                 else self._bucket(n))
        inflight = int(self._flight is not None)
        with wall.span("engine.dispatch", width=width, inflight=inflight):
            exec_slots, exec_tokens, exec_index = (batch.slot_array(), tokens,
                                                   index)
            if width > n:
                pad = width - n
                exec_slots = np.concatenate(
                    [exec_slots, np.repeat(exec_slots[:1], pad)])
                exec_tokens = np.concatenate(
                    [tokens, np.repeat(tokens[:1], pad, axis=0)])
                exec_index = np.concatenate([index, np.repeat(index[:1], pad)])
            self._note_shape(("packed", width))
            _, self.caches, drain = self._packed_decode(
                self.params, self.caches, exec_tokens, exec_index,
                exec_slots)
            self.resident_decode_steps += 1
            self.lookahead_steps += inflight
            ends, may_end = self._advance(ready, behind)

        if self.compute is not None:
            with wall.span("account", what="decode_charge"):
                charge = self.compute.decode_charge(
                    [float(k) for k in batch.kv_lens])
                # one PACKED per step, one DEFERRED per slot it deferred
                self.gateway.charge_compute(
                    charge.seconds, op_class=oc.DECODE_PACKED,
                    tags=(oc.PACKED,) + (oc.DEFERRED,) * len(deferred)
                    + self._degraded_tags(),
                    bound=charge.bound)
                self._charge_allreduce(n)

        # --- output drain: exactly the packed rows, nothing else (and an
        # MoE program's counts, on the same copy); the pad rows' tokens
        # ride the copy unpriced
        nbytes = int(drain.nbytes) - drain.dtype.itemsize * (width - n)
        flight = _Flight(ready, ends, width, drain,
                         StepTrace(step=self.step_count, active=n,
                                   prep_crossings=len(small_inputs),
                                   prep_bytes=sum(a.nbytes
                                                  for a in small_inputs),
                                   drain_bytes=nbytes,
                                   policy=self.policy.value, virtual_t=0.0,
                                   deferred=len(deferred), packed=n))
        if self.coalescer is not None:
            # bridge_opt: token values land now (they stay usable on-device
            # for the next step); the drain's toll joins the fused flush
            with wall.span("engine.drain"):
                host = self.coalescer.d2h(drain, op_class=oc.DRAIN_D2H,
                                          nbytes=nbytes)
            flight.trace.virtual_t = self.clock.now
            self._land(flight, host)
            self.coalescer.poll()
            return n
        self.gateway.price_d2h(nbytes, op_class=self._drain_class())
        flight.trace.virtual_t = self.clock.now
        ahead = (not may_end
                 and self.policy is not SchedulingPolicy.ASYNC_OVERLAP)
        if ahead:
            drain.copy_to_host_async()
        self.settle()
        if ahead:
            self._flight = flight
        else:
            self._land(flight)
        return n

    def _packed_decode(self, params, caches, tokens, index, slots) -> tuple:
        """Run the packed step program on the engine's device state: the
        resident last-token vector and the key go in and are replaced by
        the program's.  Returns (logits, caches, drain).

        The sampling parameters come from the lowest *resident* slot — a
        mask-independent choice, so masking cannot change which request's
        parameters sample the batch; only those that sample key the
        program."""
        sp = self.active[min(self.active)].sampling
        logits, caches, drain, self.last_tokens, self.key = \
            self._packed_program(
                params, caches, self.last_tokens, self.key, tokens, index,
                slots, SamplingParams(temperature=sp.temperature,
                                      top_k=sp.top_k))
        return logits, caches, drain

    def _advance(self, ready: list, behind) -> tuple[list, bool]:
        """A dispatched step's bookkeeping that no token value decides: each
        row's position and step count move on, and whether the step ends
        it by length.  ``behind``: the rows of the step in flight.  Returns
        (per row, ends by length; whether any row may end, by length or at
        its stop token)."""
        ends = [self._ends(s, self.active[s], behind) for s in ready]
        may_end = any(self._may_end(s, self.active[s], behind)
                      for s in ready)
        for s in ready:
            self.active[s].index += 1
            self.active[s].decode_steps += 1
        return ends, may_end

    def _ends(self, slot: int, req: Request, behind) -> bool:
        """Whether ``req``'s next step ends it by length.  ``behind``: the
        rows of the step in flight, whose tokens the host has not yet."""
        # tokens after the step: the host's, the step in flight's, its own
        outputs = len(req.output_tokens) + (slot in behind) + 1
        return (outputs >= req.sampling.max_new_tokens
                or req.index + 1 >= self.max_len - 1)

    def _may_end(self, slot: int, req: Request, behind) -> bool:
        """Whether ``req``'s next step can end it: by length, or at a stop
        token."""
        return req.sampling.stop_token >= 0 or self._ends(slot, req, behind)

    def _may_end_first(self, req: Request) -> bool:
        """Whether a queued request's first decode step can end it: the
        prefill gives its first token and sets its index to the prompt's
        length."""
        sp = req.sampling
        return (sp.stop_token >= 0 or sp.max_new_tokens <= 2
                or len(req.prompt) + 1 >= self.max_len - 1)

    def settle(self) -> None:
        """Drain and consume the packed step in flight, if one is."""
        flight, self._flight = self._flight, None
        if flight is not None:
            self._land(flight)

    # -- step plumbing -----------------------------------------------------------------

    def _emit_prep(self, small_inputs: list) -> None:
        """Cross one step's small modelled input arrays under the active
        policy: coalesced (bridge_opt), fresh-staging per array (async — the
        44x class), or one batched registered crossing (sync/worker).  No
        program reads them, so the batched crossing is priced and nothing
        moves; the step's executed inputs go up with the step."""
        if self.coalescer is not None:
            prep_class = (oc.ALLOC_H2D
                          if self.policy is SchedulingPolicy.ASYNC_OVERLAP
                          else oc.PREP_BATCHED_H2D)
            for arr in small_inputs:
                self.coalescer.h2d(arr, op_class=prep_class)
        elif self.policy is SchedulingPolicy.ASYNC_OVERLAP:
            for arr in small_inputs:
                self.gateway.h2d(arr, op_class=oc.ALLOC_H2D,
                                 reuse_staging=False)
        elif self.gateway.defaults.batch_small_crossings:
            self.gateway.price_batch_h2d(small_inputs,
                                         op_class=oc.PREP_BATCHED_H2D)
        else:
            self.gateway.batch_h2d(small_inputs, op_class=oc.PREP_BATCHED_H2D)

    def _drain_class(self) -> str:
        """The op class of a packed step's gateway drain under the active
        policy (the policy-defining crossing)."""
        return {SchedulingPolicy.ASYNC_OVERLAP: oc.DRAIN_D2H_NONBLOCKING,
                SchedulingPolicy.WORKER_DRAIN: oc.WORKER_DRAIN,
                }.get(self.policy, oc.DRAIN_D2H)

    def _land(self, flight: _Flight, host: Optional[np.ndarray] = None) -> None:
        """Copy a priced step's drain to the host (unless ``host`` holds it
        already) and consume its tokens."""
        trace = flight.trace
        if host is None:
            with wall.span("engine.drain"):
                host = to_host(flight.drain, self._drain_class(),
                               trace.drain_bytes)
        with wall.span("engine.consume"):
            tokens = self._take_routed(np.asarray(host), trace.packed,
                                       flight.width)
            self.trace.append(trace)
            self._consume(flight, tokens)

    def _take_routed(self, host: np.ndarray, n: int, width: int) -> np.ndarray:
        """The first ``n`` of the ``width`` tokens a program drained.  In an
        MoE model the program's (MoE layers, experts held) assignment counts
        follow the tokens: they are added to ``routed_assignments`` and put
        on an ``engine.routed`` span (attr ``counts``)."""
        if host.shape[0] > width:
            counts = host[width:].reshape(-1, self.cfg.experts_held)
            if self.routed_assignments is None:
                self.routed_assignments = np.zeros(counts.shape, np.int64)
            self.routed_assignments += counts
            with wall.span("engine.routed", counts=counts):
                pass
        return host[:n]

    def _consume(self, flight: _Flight, tokens: np.ndarray) -> None:
        """Append each row's drained token (``tokens`` by packed row
        position), stamped with the virtual time its drain was priced, and
        retire the requests it ends (only a step drained in its own tick
        ends one, so that time is now)."""
        t = flight.trace.virtual_t
        for pos, s in enumerate(flight.ready):
            req = self.active[s]
            tok = int(tokens[pos])
            req.output_tokens.append(tok)
            if self.obs is not None:
                self.obs.spans.on_token(req.request_id, t)
            if flight.ends[pos] or tok == req.sampling.stop_token:
                self._release(req)

    def run(self, max_steps: int = 10_000) -> dict:
        steps = 0
        while (self.queue or self.active) and steps < max_steps:
            if self.step() == 0 and not self.queue:
                break
            steps += 1
        self.settle()
        if self.coalescer is not None:
            self.coalescer.barrier()    # nothing queued survives a run
        return self.stats()

    def stats(self) -> dict:
        total_tokens = sum(len(r.output_tokens) for r in self.finished)
        ttfts = [r.first_token_t - r.enqueue_t for r in self.finished
                 if r.first_token_t is not None]
        return {
            "finished": len(self.finished),
            "total_tokens": total_tokens,
            "virtual_time_s": self.clock.now,
            "bridge_time_s": self.gateway.stats.bridge_time_s,
            "compute_time_s": self.gateway.stats.compute_time_s,
            "crossings": (self.gateway.stats.h2d_crossings
                          + self.gateway.stats.d2h_crossings),
            "mean_ttft_s": float(np.mean(ttfts)) if ttfts else 0.0,
            "steps": self.step_count,
            "resident_decode_steps": self.resident_decode_steps,
            "lookahead_steps": self.lookahead_steps,
            "routed_assignments": (None if self.routed_assignments is None
                                   else self.routed_assignments.tolist()),
            "overlap": self.overlap.stats_dict(),
        }
