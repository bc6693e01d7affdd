"""True positives that entered a lane's pending-prediction buffer ahead of
one already pending there (a later fault's window opening first), summed
over the chunks and devices of the window's last sweep: the program's
count ``jax_sim.LAST_TIMINGS["tp_overtakes"]``.  A program without the
count gives nothing."""


def read(ctx):
    from repro.core import jax_sim

    v = jax_sim.LAST_TIMINGS.get("tp_overtakes")
    return float(v.sum()) if v is not None and v.size else None
