"""Scheduler: median time from scheduled arrival to the start of the tick
that admitted the request, over the requests arriving in the window (one
still queued at the close counts the time waited so far)."""

from chipbench import stats


def read(run):
    w = run.window
    v = stats.percentile(stats.queue_waits(w.recs, w.t_open, w.t_close), 50)
    return None if v is None else 1e3 * v
