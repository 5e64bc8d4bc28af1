"""Engine prefill: host time of one admission (the program's
``engine.prefill`` span: prompt upload, the prompt program's dispatch, the
cache insert, the first token's sampling and drain) less its wait in
``bridge.wait`` for the device, in milliseconds: the median over the
requests admitted in the untraced stretch of the window."""

from chipbench import spans


def read(run):
    recs = spans.window(run)
    if recs is None:
        return None
    tree = spans.Tree(recs)
    return spans.median_ms([p.end_ns - p.start_ns - tree.time_in(p, {spans.WAIT})
                            for p in tree.named(spans.PREFILL)])
