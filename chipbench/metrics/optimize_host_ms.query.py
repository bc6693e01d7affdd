"""Host time of one query's ``optimize`` call, in milliseconds: the
program's ``repro.optimize`` span less its ``repro.optimize.solve``
(placement, the Newton dispatch and the fetch), mean over the calls
that opened and closed in the traced window."""

from chipbench import program_trace as P


def read(ctx):
    return P.mean_ms(
        P.recorded(), "repro.optimize",
        lambda c: c["repro.optimize"] - c["repro.optimize.solve"],
    )
