"""The optimiser's time per query, in milliseconds: the host clock around
``repro.core.optimize(..., method="newton")``, which returns host arrays,
so the time covers its device work and the fetch."""


def read(ctx):
    v = ctx["layer"].get("newton_s") or []
    return 1e3 * sum(v) / len(v) if v else None
