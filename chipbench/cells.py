"""Grid cells of a deployment, built from its configuration file alone.

:func:`program_cells` builds the program's :class:`ExperimentCell` list
through its public constructors; :func:`reference_cells` builds the same
cells for the plain reference from the file's numbers, with periods the
reference computes itself.  Both label a cell
``<law>/<predictor>/N<nodes>/<strategy>``.

Each entry of the file's ``strategies`` names the program's strategy
factory (a function of ``repro.core.simulator``), the arguments it takes
(``platform``, ``predictor``) and the mode the reference runs it in, so a
deployment with other strategies is a new configuration file.
"""

from __future__ import annotations


def law_tag(law: dict) -> str:
    if law["law"] == "exponential":
        return "exp"
    return f"{law['law']}{law['shape']}"


def label(law: dict, pred: dict, n: int, strategy: dict) -> str:
    return f"{law_tag(law)}/{pred['name']}/N{n}/{strategy['name']}"


def points(cfg: dict):
    """Every (law, predictor, platform size) point of the deployment."""
    return [
        (law, pred, n)
        for law in cfg["fault_laws"]
        for pred in cfg["predictors"]
        for n in cfg["N"]
    ]


def program_objects(cfg: dict, law: dict, pred: dict, n: int):
    """The program's (platform, predictor, failure law) of one point."""
    from repro.core.events import exponential, weibull
    from repro.core.waste import Platform, PredictorModel

    plat = Platform.from_components(
        cfg["mu_ind_s"], n, cfg["C_s"], cfg["D_s"], cfg["R_s"], M=cfg["M_s"]
    )
    pm = PredictorModel(
        pred["recall"], pred["precision"], lead=cfg["lead_s"],
        window=cfg["window_s"],
    )
    dist = exponential() if law["law"] == "exponential" else weibull(law["shape"])
    return plat, pm, dist


def program_cell(cfg: dict, law, pred, n, strategy: dict, strat=None):
    """One :class:`ExperimentCell`; ``strat`` overrides the factory's
    strategy (a query's optimised period)."""
    from repro.core import simulator as S
    from repro.experiments import ExperimentCell

    plat, pm, dist = program_objects(cfg, law, pred, n)
    if strat is None:
        args = {"platform": plat, "predictor": pm}
        strat = getattr(S, strategy["factory"])(
            *(args[a] for a in strategy["args"])
        )
    return ExperimentCell(
        label=label(law, pred, n, strategy),
        work=cfg["work_s"],
        platform=plat,
        predictor=pm,
        strategy=strat,
        fault_dist=dist,
        horizon_factor=cfg["horizon_factor"],
    )


def program_cells(cfg: dict) -> list:
    return [
        program_cell(cfg, law, pred, n, s)
        for law, pred, n in points(cfg)
        for s in cfg["strategies"]
    ]


def reference_cell(cfg: dict, law, pred, n, strategy: dict, T: float,
                   q: float = 1.0) -> dict:
    """The plain reference's view of one cell, at period ``T``."""
    return {
        "label": label(law, pred, n, strategy),
        "law": law["law"],
        "shape": float(law["shape"]),
        "mu": cfg["mu_ind_s"] / n,
        "recall": pred["recall"],
        "precision": pred["precision"],
        "horizon": cfg["horizon_factor"] * cfg["work_s"],
        "W": cfg["work_s"],
        "C": cfg["C_s"],
        "D": cfg["D_s"],
        "R": cfg["R_s"],
        "M": cfg["M_s"],
        "T": T,
        "mode": strategy["reference_mode"] if q > 0.0 else "none",
    }


def reference_cells(cfg: dict, ref) -> list:
    """Every cell of the grid at the reference's own optimal periods."""
    out = []
    for law, pred, n in points(cfg):
        mu = cfg["mu_ind_s"] / n
        for s in cfg["strategies"]:
            T = float(ref.period(s["name"], cfg["C_s"], mu, pred["recall"]))
            out.append(reference_cell(cfg, law, pred, n, s, T))
    return out
