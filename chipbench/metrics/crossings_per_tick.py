"""Bridge: real host-device transfers a decode-only tick makes (the
program's ``bridge.h2d`` and ``bridge.d2h`` spans), the mean over the
untraced stretch of the window."""

from chipbench import spans


def read(run):
    recs = spans.window(run)
    if recs is None:
        return None
    tree = spans.Tree(recs)
    counts = [tree.count(t, spans.CROSSINGS) for t in spans.decode_ticks(tree)]
    return sum(counts) / len(counts) if counts else None
