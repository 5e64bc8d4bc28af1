"""95th percentile of every gap between consecutive tokens of a request,
the later token delivered in the window."""

from chipbench import stats


def read(run):
    w = run.window
    v = stats.percentile(stats.token_gaps(w.recs, w.t_open, w.t_close), 95)
    return None if v is None else 1e3 * v
