"""Engine decode: device time per run of the programs that run absorbed
latent-attention decode (ops traced under ``KERNEL_mla_decode``: the
packed decode step), from the trace, in milliseconds."""


def read(run):
    if run.trace is None:
        return None
    ids = {o.program for o in run.trace.scoped(r"KERNEL_mla_decode")}
    s, n = run.trace.program_time(ids)
    return 1e3 * s / n if n else None
