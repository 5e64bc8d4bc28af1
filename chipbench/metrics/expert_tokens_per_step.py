"""MoE routing: assignments each held expert takes per MoE layer in a
decode-only tick's packed step (the program's ``engine.routed`` counts),
the mean over the experts, the layers and the ticks of the untraced stretch
of the window."""

from chipbench import routed, spans


def read(run):
    recs = spans.window(run)
    if recs is None:
        return None
    tree = spans.Tree(recs)
    means = [float(routed.counts(r).mean()) for t in spans.decode_ticks(tree)
             for r in tree.inside(t, {routed.ROUTED})]
    return sum(means) / len(means) if means else None
