"""Kernels: decode attention's share of its roofline, in percent.

The least time is what the work of the traced window's decode rows needs at
each row's actual KV length, whatever cache capacity the program reads: the
larger of reading each layer's keys and values once at the chip's HBM
bandwidth (the bound that applies at decode) and the scores and weighted
sums at its bf16 peak.  The measured time is the device time of the ops
traced under ``KERNEL_paged_attention`` in the same window."""

from chipbench import flops
from chipbench.peaks import peaks
from chipbench.trace_reduce import CONTAINERS


def read(run):
    if run.trace is None:
        return None
    spans = sorted((o.start, o.end) for o in
                   run.trace.scoped(r"KERNEL_paged_attention")
                   if o.label.split(" ")[0] not in CONTAINERS)
    measured, reach = 0, None
    for a, b in spans:                       # the union of the op intervals
        if reach is None or a > reach:
            measured += b - a
            reach = b
        elif b > reach:
            measured += b - reach
            reach = b
    rows = [k for t in run.trace.ticks(run.window.ticks) for k in t.decode_kv]
    if not rows or measured <= 0:
        return None
    p = peaks(run.device_kind)
    least = max(
        sum(flops.attention_bytes(run.model, k) for k in rows)
        / p["hbm_bytes_per_s"],
        sum(flops.attention_flops(run.model, k) for k in rows)
        / p["bf16_flops_per_s"])
    return 100.0 * least / (measured / 1e9)
