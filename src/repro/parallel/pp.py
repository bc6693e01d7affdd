"""Pipeline parallelism: GPipe-style microbatched stage execution.

The assigned shapes are served by DP x TP (+EP/SP) on the production mesh,
but at 1000+-node scale a pipeline axis bounds the TP collective domain,
so the framework ships a composable PP layer:

* the layer stack is split into ``n_stages`` contiguous stages;
* microbatches flow through stages in the classic GPipe schedule
  (fill, steady state, drain) implemented as a ``lax.scan`` over
  ``n_micro + n_stages - 1`` ticks with a ``collective_permute`` ring
  between stage neighbours each tick;
* runs under ``shard_map`` over a "stage" mesh axis; each rank holds only
  its stage's parameters (pipeline-sharded weights).

``pipeline_apply`` is forward-only-composable (jax differentiates through
the scan + ppermute); ``bubble_fraction`` gives the schedule's idle share
(n_stages - 1) / (n_micro + n_stages - 1) for the napkin math used when
choosing n_micro.

Validated in tests/test_pp.py: pipelined == sequential stack execution on
a forced multi-device mesh, plus the bubble accounting.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_apply", "bubble_fraction"]


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """Idle fraction of the GPipe schedule."""
    ticks = n_micro + n_stages - 1
    return (n_stages - 1) / ticks


def pipeline_apply(
    stage_fn: Callable,
    params_stacked,
    x: jax.Array,  # (n_micro, micro_batch, ...) microbatched activations
    mesh: jax.sharding.Mesh,
    axis: str = "stage",
):
    """Run ``stage_fn(stage_params, activation) -> activation`` as a
    GPipe pipeline over the ``axis`` mesh dimension.

    params_stacked: pytree with leading dim n_stages (sharded over `axis`).
    Returns activations of shape (n_micro, micro_batch, ...) — the output
    of the final stage per microbatch.
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    ticks = n_micro + n_stages - 1

    def local(params_local, x_local):
        # params_local: stage's params (leading dim 1); x_local: full
        # microbatch stream replicated (simple variant; a production
        # deployment feeds stage 0 only)
        sid = jax.lax.axis_index(axis)
        p_stage = jax.tree.map(lambda a: a[0], params_local)
        micro = x_local  # (n_micro, mb, ...)
        mb_shape = micro.shape[1:]

        def tick(carry, t):
            buf, outputs = carry  # buf: activation entering this stage
            # stage s processes microbatch (t - s) when 0 <= t-s < n_micro
            mb_idx = t - sid
            active = (mb_idx >= 0) & (mb_idx < n_micro)
            # stage 0 ingests fresh microbatches; others use the ring buf
            inject = jnp.where(
                sid == 0,
                micro[jnp.clip(mb_idx, 0, n_micro - 1)],
                buf,
            )
            y = stage_fn(p_stage, inject)
            y = jnp.where(active, y, buf)
            # last stage records its finished microbatch
            outputs = jnp.where(
                active & (sid == n_stages - 1),
                outputs.at[jnp.clip(mb_idx, 0, n_micro - 1)].set(y),
                outputs,
            )
            # ring: stage s -> s+1
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf_next = jax.lax.ppermute(y, axis, perm)
            return (buf_next, outputs), None

        buf0 = jnp.zeros(mb_shape, x.dtype)
        outs0 = jnp.zeros((n_micro,) + mb_shape, x.dtype)
        (_, outputs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(ticks)
        )
        # outputs live on the last stage; broadcast to all ranks via psum
        # of the one-hot-owned buffer (cheap relative to the compute)
        owned = jnp.where(sid == n_stages - 1, outputs, jnp.zeros_like(outputs))
        return jax.lax.psum(owned, axis)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(params_stacked, x)
