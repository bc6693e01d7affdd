"""Int8 gradient compression with error feedback (distributed-optimization
trick for the DP all-reduce).

Classic EF-SGD/1-bit-Adam structure: the *transmitted* gradient is an int8
blockwise quantization of (gradient + residual); the quantization error is
carried to the next step.  Under GSPMD the data-parallel reduction of a jit
train step is implicit, so the wire-format win is realized via the explicit
``shard_map`` reduction in :func:`dp_allreduce_int8`; the pure functions
here are also used by the checkpoint codec tests and the convergence test.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = [
    "compress_gradients",
    "decompress_gradients",
    "ef_compress_step",
    "dp_allreduce_int8",
]

_BLOCK = 256


def _blockwise(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % _BLOCK
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, _BLOCK)
    scale = jnp.maximum(jnp.max(jnp.abs(blocks), axis=1) / 127.0, 1e-12)
    q = jnp.clip(jnp.round(blocks / scale[:, None]), -127, 127).astype(jnp.int8)
    return q, scale


def compress_gradients(tree):
    """tree of f32/bf16 -> tree of (int8 blocks, f32 scales)."""
    return jax.tree.map(lambda g: _blockwise(g.astype(jnp.float32)), tree)


def decompress_gradients(ctree, shapes_tree):
    def leaf(c, ref):
        q, s = c
        x = (q.astype(jnp.float32) * s[:, None]).reshape(-1)
        n = ref.size
        return x[:n].reshape(ref.shape)

    return jax.tree.map(
        leaf, ctree, shapes_tree, is_leaf=lambda x: isinstance(x, tuple)
    )


def ef_compress_step(grads, residual):
    """Error-feedback compression: returns (decompressed grads, new residual).

    residual has the same structure/shapes as grads (zeros at step 0)."""
    def leaf(g, r):
        g32 = g.astype(jnp.float32) + r
        q, s = _blockwise(g32)
        deq = (q.astype(jnp.float32) * s[:, None]).reshape(-1)[: g.size].reshape(
            g.shape
        )
        return deq.astype(g.dtype), (g32 - deq).astype(jnp.float32)

    flat_g, tdef = jax.tree.flatten(grads)
    flat_r = tdef.flatten_up_to(residual)
    out = [leaf(g, r) for g, r in zip(flat_g, flat_r)]
    return tdef.unflatten([o[0] for o in out]), tdef.unflatten([o[1] for o in out])


def dp_allreduce_int8(x: jax.Array, mesh, axis: str = "data") -> jax.Array:
    """All-reduce over the data axis moving int8 on the wire.

    shard_map kernel: quantize the local shard -> psum the int8 payload as
    int32 partial sums of dequantized blocks is NOT int8 on the wire, so we
    instead all_gather the (int8, scale) pairs and reduce locally: wire
    bytes = (N-1)/N * (1 byte + 4/256) per element versus 2x4 bytes for a
    ring all-reduce of f32 — a ~7x wire reduction at the cost of a local
    N-way sum."""

    def kern(xs):
        q, s = _blockwise(xs)
        qg = jax.lax.all_gather(q, axis)  # (N, blocks, BLOCK) int8
        sg = jax.lax.all_gather(s, axis)
        deq = qg.astype(jnp.float32) * sg[..., None]
        total = deq.sum(axis=0).reshape(-1)[: xs.size].reshape(xs.shape)
        return total

    spec = P(axis)
    return jax.shard_map(
        kern, mesh=mesh, in_specs=(spec,), out_specs=spec, check_vma=False
    )(x)
