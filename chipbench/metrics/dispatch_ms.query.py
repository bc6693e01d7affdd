"""Dispatch per query, in milliseconds: the program's
``repro.engine.dispatch`` spans (the chunks' ``device_put``s and
launch) of one ``run_grid`` call, mean over the calls that opened and
closed in the traced window."""

from chipbench import program_trace as P


def read(ctx):
    return P.mean_ms(
        P.recorded(), "repro.run_grid", lambda c: c["repro.engine.dispatch"]
    )
