"""Output tokens delivered to the client in the window, over its length."""

from chipbench import stats


def read(run):
    w = run.window
    return stats.tokens_in(w.recs, w.t_open, w.t_close) / w.seconds
