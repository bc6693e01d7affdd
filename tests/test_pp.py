"""Pipeline parallelism: pipelined == sequential, bubble accounting.

Runs in a subprocess (forced 4 host devices for the stage axis)."""

import os
import subprocess
import sys
import textwrap

import pytest

from repro.parallel.pp import bubble_fraction


def test_bubble_fraction():
    assert bubble_fraction(4, 4) == pytest.approx(3 / 7)
    assert bubble_fraction(4, 16) == pytest.approx(3 / 19)
    assert bubble_fraction(1, 8) == 0.0  # no pipeline, no bubble


SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.parallel.pp import pipeline_apply

    from repro.launch.mesh import make_auto_mesh

    mesh = make_auto_mesh((4,), ("stage",))
    S, D, MB, NM = 4, 16, 8, 6
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((S, D, D)) * 0.3, jnp.float32)

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    params = {"w": w}
    x = jnp.asarray(rng.standard_normal((NM, MB, D)), jnp.float32)

    # sequential reference
    ref = x
    for s in range(S):
        ref = jnp.tanh(ref @ w[s])

    out = pipeline_apply(stage_fn, params, x, mesh, axis="stage")
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 1e-5, f"pipeline mismatch {err}"

    # differentiable through the pipeline
    def loss(wq):
        o = pipeline_apply(stage_fn, {"w": wq}, x, mesh, axis="stage")
        return jnp.sum(o * o)

    g = jax.grad(loss)(w)
    assert bool(jnp.all(jnp.isfinite(g)))
    assert float(jnp.max(jnp.abs(g))) > 0
    print("PP_CHECK_OK")
    """
)


@pytest.mark.slow
def test_pipeline_matches_sequential(tmp_path):
    script = tmp_path / "pp_check.py"
    script.write_text(SCRIPT)
    proc = subprocess.run(
        [sys.executable, str(script)],
        capture_output=True,
        text=True,
        timeout=900,
        env={
            **os.environ,
            "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src"),
        },
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "PP_CHECK_OK" in proc.stdout
