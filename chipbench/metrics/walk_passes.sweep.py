"""Passes of the lane machine's prediction walk per chunk: the program's
count ``jax_sim.LAST_TIMINGS["walk_passes"]`` of the window's last sweep
(the walk's passes summed over a chunk's outer iterations, fetched with
the results), mean over its chunks and devices.  A program without the
count gives nothing."""


def read(ctx):
    from repro.core import jax_sim

    v = jax_sim.LAST_TIMINGS.get("walk_passes")
    return float(v.mean()) if v is not None and v.size else None
