"""Engine decode: mean host wall time of the window's ticks that decoded
and admitted nothing, in milliseconds."""


def read(run):
    w = run.window
    dts = [t.t1 - t.t0 for t in w.ticks
           if w.t_open <= t.t0 and t.t1 <= w.t_close
           and t.decode_kv and not t.admitted]
    return 1e3 * sum(dts) / len(dts) if dts else None
