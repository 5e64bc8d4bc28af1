"""Whole model step: the model operations of every token prefilled and
decoded in the traced window (2 x the weights each token multiplies
through, plus attention at its actual context and the recurrence of any
state-space heads), over the window's length times the chip's bf16 peak, in
percent."""

from chipbench import flops
from chipbench.peaks import peaks


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    ticks = run.trace.ticks(run.window.ticks)
    if not ticks:
        return None
    work = sum(flops.prefill_flops(run.model, n)
               for t in ticks for n in t.prefill_lens)
    work += sum(flops.decode_flops(run.model, k)
                for t in ticks for k in t.decode_kv)
    peak = peaks(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * work / (run.trace.window_s * peak)
