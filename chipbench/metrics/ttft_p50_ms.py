"""Median time from scheduled arrival to first token, over the requests
arriving in the window (one still waiting at the close counts the time
waited so far)."""

from chipbench import stats


def read(run):
    w = run.window
    v = stats.percentile(stats.ttfts(w.recs, w.t_open, w.t_close), 50)
    return None if v is None else 1e3 * v
