"""Iterations of the lane machine's outer while loop per chunk: the
program's count ``jax_sim.LAST_TIMINGS["loop_iters"]`` of the window's
last sweep (fetched with the results), mean over its chunks and
devices."""


def read(ctx):
    from repro.core import jax_sim

    v = jax_sim.LAST_TIMINGS.get("loop_iters")
    return float(v.mean()) if v is not None and v.size else None
