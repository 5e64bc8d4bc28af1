"""Engine prefill: device time per prompt, from the trace, in milliseconds:
the prompt program (``prefill_logits_cache``) and the programs that insert
its cache into the resident one (the engine's eager ``.at[slot].set``,
which runs as ``scatter`` programs), over the prompt program's runs."""


def read(run):
    if run.trace is None:
        return None
    prompt, n = run.trace.module_time(r"^prefill_logits_cache$")
    insert, _ = run.trace.module_time(r"^scatter$")
    return 1e3 * (prompt + insert) / n if n else None
