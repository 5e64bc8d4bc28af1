"""Virtual-clock accounting: host time a decode-only tick spends pricing
and recording on the virtual clock (the program's ``account`` spans: ladder
sync, ready slots, the compute charge, each crossing's pricing and tape
record with its observatory hook, coalescer polls), in milliseconds: the
median over the untraced stretch of the window."""

from chipbench import spans


def read(run):
    recs = spans.window(run)
    if recs is None:
        return None
    tree = spans.Tree(recs)
    return spans.median_ms([tree.time_in(t, {spans.ACCOUNT})
                            for t in spans.decode_ticks(tree)])
