"""Host time of one query's ``run_grid`` call, in milliseconds: the
program's ``repro.run_grid`` span less the ``repro.engine.wait`` spans
inside it (the time the host held the call, not the device), mean over
the calls that opened and closed in the traced window."""

from chipbench import program_trace as P


def read(ctx):
    return P.mean_ms(
        P.recorded(), "repro.run_grid",
        lambda c: c["repro.run_grid"] - c["repro.engine.wait"],
    )
