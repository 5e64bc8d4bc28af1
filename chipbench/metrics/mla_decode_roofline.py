"""Kernels: absorbed latent-attention decode's share of its roofline, in
percent.

The least time is what the traced window's decode rows need at each row's
actual KV length, whatever cache capacity the program reads: the larger of
reading each layer's latent rows (the latent and the shared rope key) once
at the chip's HBM bandwidth, and the latent and rope scores and the latent
weighted sum of every head at its bf16 peak.  The measured time is the
union of the device intervals, in the programs that run it (the packed
steps), of the ops traced under ``KERNEL_mla_decode`` (scores, softmax,
weighted sum) and of the row stack that feeds them: the
dynamic-update-slice fusions that copy each decoded row's latents
(``[width, max_len, kv_lora_rank]``) and rope keys (``[width, max_len,
rope_head_dim]``) out of the resident cache.  The compiler emits those
fusions with no source op, so they are found by their kind and shape; the
packed step has no other dynamic-update-slice."""

import re

from chipbench import flops_mla_moe as fm
from chipbench.peaks import peaks
from chipbench.trace_reduce import CONTAINERS, _interval_union

SCOPE = "KERNEL_mla_decode"


def row_stack(label: str, c: dict) -> bool:
    """Whether an op (by its ``op_label``) stacks decoded rows' latents or
    rope keys: a dynamic-update-slice of a rank-3 buffer whose last axis is
    the latent rank or the rope dims."""
    m = re.fullmatch(r"(\S*dynamic-update-slice\S*) [a-z0-9]+\[(\d+),(\d+),(\d+)\]",
                     label)
    return bool(m) and int(m.group(4)) in (c["kv_lora_rank"],
                                           c["rope_head_dim"])


def read(run):
    if run.trace is None:
        return None
    steps = {o.program for o in run.trace.scoped(SCOPE)}
    ivs = [(o.start, o.end) for o in run.trace.ops
           if o.program in steps
           and (SCOPE in o.scope or row_stack(o.label, run.model))
           and o.label.split(" ")[0] not in CONTAINERS]
    measured = sum(b - a for a, b in _interval_union(ivs))
    rows = [k for t in run.trace.ticks(run.window.ticks) for k in t.decode_kv]
    if not rows or measured <= 0:
        return None
    p = peaks(run.device_kind)
    least = max(
        sum(fm.mla_decode_bytes(run.model, k) for k in rows)
        / p["hbm_bytes_per_s"],
        sum(fm.mla_decode_flops(run.model, k) for k in rows)
        / p["bf16_flops_per_s"])
    return 100.0 * least / (measured / 1e9)
