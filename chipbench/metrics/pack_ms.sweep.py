"""Host packing per sweep: ``jax_sim.LAST_TIMINGS["pack_s"]`` of each sweep
in the window, in milliseconds (the chunk pipeline's host share)."""


def read(ctx):
    v = ctx["layer"].get("pack_s") or []
    return 1e3 * sum(v) / len(v) if v else None
