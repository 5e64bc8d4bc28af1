"""Engine decode: device time of the packed decode program per run, from
the trace, in milliseconds.  The programs are found by what they run, the
ops traced under ``KERNEL_paged_attention`` (decode attention), not by
name: the engine jits ``functools.partial(packed_step, model)``, which JAX
names ``_unknown``, as it would any other such partial."""


def read(run):
    if run.trace is None:
        return None
    ids = {o.program for o in run.trace.scoped(r"KERNEL_paged_attention")}
    s, n = run.trace.program_time(ids)
    return 1e3 * s / n if n else None
