"""Subprocess body for test_sharding.py: numerical equivalence of the
sharded (GSPMD + shard_map MoE) execution vs single-device, on 8 forced
host devices.  Run directly:  python tests/_sharded_check.py
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch.steps import (
    build_model,
    build_train_step,
    decode_arg_structs,
    train_arg_structs,
)
from repro.models.layers import RuntimeFlags
from repro.models.transformer import LanguageModel
from repro.optim.adamw import adamw_init

from repro.launch.mesh import make_auto_mesh

assert len(jax.devices()) == 8, jax.devices()

mesh = make_auto_mesh((2, 4), ("data", "model"))
# chunked attention exercised via tiny dense_attn_max; capacity factor is
# raised so no MoE tokens drop — capacity dropping is legitimately
# locality-dependent (per-DP-group vs global), which would differ between
# the sharded and single-device runs by design
FLAGS = RuntimeFlags(dense_attn_max=16, kv_chunk=8, moe_capacity_factor=4.0)


def check_arch(name: str) -> None:
    cfg = configs.get(name).reduced()
    model_1d = LanguageModel(cfg, rules=None, flags=FLAGS)
    model_sh, rules = build_model(cfg, mesh, FLAGS)

    params = model_1d.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    B, S = 4, 32
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    }
    if cfg.frontend:
        batch["frontend"] = jnp.asarray(
            rng.standard_normal((B, cfg.frontend_prefix, cfg.d_model)) * 0.02,
            jnp.bfloat16,
        )

    loss_1d, _ = jax.jit(model_1d.loss_fn)(params, batch)

    with mesh:
        loss_sh, _ = jax.jit(model_sh.loss_fn)(params, batch)
    err = abs(float(loss_1d) - float(loss_sh))
    assert err < 5e-2, f"{name}: sharded loss mismatch {loss_1d} vs {loss_sh}"

    # decode parity
    max_seq = S + (cfg.frontend_prefix or 0) + 8
    logits_1d, cache_1d = jax.jit(
        lambda p, t, f: model_1d.prefill(p, t, max_seq, f)
    )(params, batch["tokens"], batch.get("frontend"))
    with mesh:
        logits_sh, cache_sh = jax.jit(
            lambda p, t, f: model_sh.prefill(p, t, max_seq, f)
        )(params, batch["tokens"], batch.get("frontend"))
    # bf16 reduction-order noise flips argmax among near-ties on tiny
    # random-weight models; assert numeric closeness of the logits and a
    # loose argmax majority instead
    l1 = np.asarray(logits_1d[:, -1], np.float32)
    l2 = np.asarray(logits_sh[:, -1], np.float32)
    lerr = np.abs(l1 - l2).max()
    # bf16 partial-sum reordering through 8+ residual layers yields O(0.1-1)
    # per-logit noise on random-weight reduced models; the token-mean loss
    # (checked above to 5e-2) is the meaningful numerical invariant
    assert lerr < 1.5, f"{name}: prefill logits diverge ({lerr})"
    tok_1d = l1.argmax(-1)
    tok_sh = l2.argmax(-1)
    agree = (tok_1d == tok_sh).mean()
    assert agree >= 0.5, f"{name}: prefill argmax agreement {agree}"
    print(f"  {name}: loss err {err:.2e}, logits err {lerr:.3f}, "
          f"agreement {agree:.2f}", flush=True)


def check_train_step_compiles_and_runs(name: str) -> None:
    """Full train step with ZeRO shardings executes on the 2x4 mesh."""
    cfg = configs.get(name).reduced()
    model, rules = build_model(cfg, mesh, FLAGS)
    from repro.configs.base import ShapeConfig

    shape = ShapeConfig("tiny", 32, 4, "train")
    step = build_train_step(model, micro_batches=2)
    args, in_sh, out_sh = train_arg_structs(model, shape, rules)
    params = model.init(jax.random.PRNGKey(1))
    opt = adamw_init(params, quantize=cfg.optimizer == "adamw8bit")
    rng = np.random.default_rng(1)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32)
    }
    if cfg.frontend:
        batch["frontend"] = jnp.asarray(
            rng.standard_normal((4, cfg.frontend_prefix, cfg.d_model)) * 0.02,
            jnp.bfloat16,
        )
    with mesh:
        fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
        p2, o2, metrics = fn(params, opt, batch)
        p3, o3, m2 = fn(p2, o2, batch)
    assert np.isfinite(float(metrics["loss"])), name
    assert float(m2["loss"]) < float(metrics["loss"]) + 1.0
    print(f"  {name}: sharded train step loss {float(metrics['loss']):.3f} -> "
          f"{float(m2['loss']):.3f}", flush=True)


for arch in ["qwen3-moe-30b-a3b", "granite-8b", "rwkv6-7b", "jamba-1.5-large-398b"]:
    check_arch(arch)
for arch in ["qwen3-moe-30b-a3b", "smollm-135m"]:
    check_train_step_compiles_and_runs(arch)
print("SHARDED_CHECK_OK")
