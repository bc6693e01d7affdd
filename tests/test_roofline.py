"""HLO cost-parser validation: hand-computable cases in a subprocess
(forced multi-device), checking scan trip-count weighting and collective
wire-byte factors."""

import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.launch.hlo_analysis import analyze_hlo

    from repro.launch.mesh import make_auto_mesh

    mesh = make_auto_mesh((2, 4), ("data", "model"))

    L, D, F, B = 3, 64, 128, 16
    def step(w1, w2, x):
        def body(h, ws):
            a, b = ws
            return jnp.tanh(h @ a) @ b, ()
        h, _ = jax.lax.scan(body, x, (w1, w2))
        return jnp.sum(h * h)

    w1 = jax.ShapeDtypeStruct((L, D, F), jnp.float32)
    w2 = jax.ShapeDtypeStruct((L, F, D), jnp.float32)
    x = jax.ShapeDtypeStruct((B, D), jnp.float32)
    in_sh = (NamedSharding(mesh, P(None, None, "model")),
             NamedSharding(mesh, P(None, "model", None)),
             NamedSharding(mesh, P("data", None)))
    c = jax.jit(step, in_shardings=in_sh).lower(w1, w2, x).compile()
    cost = analyze_hlo(c.as_text())

    # per-device matmul flops, scan-corrected: L * (2*B/2*F/4*D + 2*B/2*D*F/4)
    expect = L * (2 * (B // 2) * (F // 4) * D + 2 * (B // 2) * D * (F // 4))
    assert abs(cost.flops - expect) / expect < 0.02, (cost.flops, expect)

    # collectives: per-iter all-reduce of f32[B/2, D] over model (g=4):
    # ring factor 2*(g-1)/g -> 1.5; plus final scalar loss all-reduce over
    # data (g=2): 4 bytes * 1.0
    per_iter = (B // 2) * D * 4 * 2 * 3 / 4
    expect_coll = L * per_iter + 4 * 1.0
    assert abs(cost.collective_bytes - expect_coll) / expect_coll < 0.02, (
        cost.collective_bytes, expect_coll)

    # XLA's own cost_analysis counts the while body once -> our number
    # must exceed it for L > 1 (older JAX returns a per-device list)
    ca = c.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    xla_flops = ca["flops"]
    assert cost.flops > xla_flops, (cost.flops, xla_flops)
    print("ROOFLINE_PARSER_OK")
    """
)


@pytest.mark.slow
def test_parser_scan_and_collectives(tmp_path):
    script = tmp_path / "parser_check.py"
    script.write_text(SCRIPT)
    # the script resolves src relative to its own dir; symlink tests layout
    proc = subprocess.run(
        [sys.executable, str(script)],
        capture_output=True,
        text=True,
        timeout=600,
        cwd=os.path.dirname(__file__),
        env={**os.environ, "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src")},
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "ROOFLINE_PARSER_OK" in proc.stdout
