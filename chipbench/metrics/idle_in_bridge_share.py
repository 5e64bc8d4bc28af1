"""Device: the share of the traced window's device idle time (the window
less the union of the chip's ops) in which the host was inside a real
host-device transfer (the program's ``bridge.h2d`` or ``bridge.d2h`` span,
on any thread), in percent.  Spans are put on the trace's clock through the
two ends of the harness's ``bench.window`` span."""

from chipbench import spans


def read(run):
    recs = spans.window(run, traced=True)
    if recs is None:
        return None
    return spans.idle_in(run.trace, recs, spans.CROSSINGS)
