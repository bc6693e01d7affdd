"""Share of the step program's device busy time in the lane machine's
nested loops (cursor priming, the prediction lookahead, faults during
downtime or recovery), in percent: the ``while`` ops nested in the outer
loop, and those before it, over the union of op intervals inside the
step program's runs in the traced window.  A TPU trace names a ``while``
op by its HLO text alone, so no scope of the program can mark them
there: their nesting does."""

from chipbench import program_trace as P


def read(ctx):
    tr = ctx.get("trace")
    return None if tr is None else P.nested_loop_share(tr)
