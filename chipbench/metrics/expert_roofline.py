"""Kernels: the held experts' share of their roofline in the packed decode
step, in percent.

The least time is what the traced window's packed steps need by their own
assignment counts (the program's ``engine.routed`` spans): the larger of
reading the weights of each (MoE layer, held expert) with at least one
assignment in a step once at the chip's HBM bandwidth, and each
assignment's three expert matrices at its bf16 peak.  The measured time is
the union of the device intervals of the ops traced under
``KERNEL_moe_experts`` (dispatch, the expert matmuls, the gated combine) in
the programs that run latent-attention decode, the packed steps."""

from chipbench import flops_mla_moe as fm, routed, spans
from chipbench.peaks import peaks
from chipbench.trace_reduce import CONTAINERS, _interval_union


def read(run):
    if run.trace is None:
        return None
    steps = {o.program for o in run.trace.scoped(r"KERNEL_mla_decode")}
    ivs = [(o.start, o.end) for o in run.trace.scoped(r"KERNEL_moe_experts")
           if o.program in steps and o.label.split(" ")[0] not in CONTAINERS]
    measured = sum(b - a for a, b in _interval_union(ivs))
    recs = spans.window(run, traced=True)
    if recs is None or measured <= 0:
        return None
    counts = routed.decode_steps(recs)
    if not counts:
        return None
    p = peaks(run.device_kind)
    least = max(
        sum(int((k > 0).sum()) for k in counts) * fm.expert_bytes(run.model)
        / p["hbm_bytes_per_s"],
        sum(int(k.sum()) for k in counts) * fm.expert_flops(run.model)
        / p["bf16_flops_per_s"])
    return 100.0 * least / (measured / 1e9)
