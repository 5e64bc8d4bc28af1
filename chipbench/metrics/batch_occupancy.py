"""Scheduler: mean rows of the window's packed decode steps
(``StepTrace.packed``) as a share of ``max_batch``, in percent."""


def read(run):
    packed = run.window.packed
    if not packed:
        return None
    return 100.0 * sum(packed) / len(packed) / run.max_batch
