"""The most true positives any lane held pending at once in the window's
last sweep, over its chunks and devices: the program's count
``jax_sim.LAST_TIMINGS["tp_pending_peak"]``, the buffer's headroom
against its slots.  A program without the count gives nothing."""


def read(ctx):
    from repro.core import jax_sim

    v = jax_sim.LAST_TIMINGS.get("tp_pending_peak")
    return float(v.max()) if v is not None and v.size else None
