"""Whole model step: the model operations of every token prefilled and
decoded in the traced window at this chip's share of the experts (2 x the
weights each token multiplies through but the routed experts', latent
attention at its actual context, and each of the program's counted
assignments to a held expert), over the window's length times the chip's
bf16 peak, in percent."""

from chipbench import flops_mla_moe as fm, routed, spans
from chipbench.peaks import peaks


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    ticks = run.trace.ticks(run.window.ticks)
    recs = spans.window(run, traced=True)
    if not ticks or recs is None:
        return None
    counts = routed.in_ticks(recs, ticks)
    if not counts:
        return None
    c = run.model
    work = sum(fm.prefill_flops(c, n) for t in ticks for n in t.prefill_lens)
    work += sum(fm.decode_flops(c, k) for t in ticks for k in t.decode_kv)
    work += sum(int(k.sum()) for k in counts) * fm.expert_flops(c)
    peak = peaks(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * work / (run.trace.window_s * peak)
