"""Bridge: host time a decode-only tick spends in real host-device
transfers (the program's ``bridge.h2d`` and ``bridge.d2h`` spans, the wait
for the device left out), in milliseconds: the median over the untraced
stretch of the window."""

from chipbench import spans


def read(run):
    recs = spans.window(run)
    if recs is None:
        return None
    tree = spans.Tree(recs)
    return spans.median_ms([tree.time_in(t, spans.CROSSINGS)
                            for t in spans.decode_ticks(tree)])
