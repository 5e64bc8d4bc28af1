"""Engine decode: host time of a decode-only tick, in milliseconds: the
median, over the untraced stretch of the window, of the program's
``sched.tick`` span less the time it waits in ``bridge.wait`` for the
device to finish the step's tokens."""

from chipbench import spans


def read(run):
    recs = spans.window(run)
    if recs is None:
        return None
    tree = spans.Tree(recs)
    return spans.median_ms([t.end_ns - t.start_ns - tree.time_in(t, {spans.WAIT})
                            for t in spans.decode_ticks(tree)])
