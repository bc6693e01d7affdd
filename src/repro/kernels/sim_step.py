"""Pallas kernel for the batch simulator's masked primitive-update step.

This is the one dense elementwise block the device simulation engine
(:mod:`repro.core.jax_sim`) executes *every* outer iteration: given the
primitive each lane decided to run (work segment / idle segment /
checkpoint), the pre-resolved next-fault date, and the lane state, it

1. applies the fault check (a fault at or before the primitive's target
   interrupts work/idle; a fault strictly before a checkpoint's end date
   aborts it — the exact-date prediction semantics of the scalar oracle),
2. advances the clock and the saved/unsaved/period-work accounting with
   masked updates, and
3. reports the outcome flags (faulted / ok / job finished / checkpoint
   committed / regular checkpoint) packed in one int32 bitfield.

Lane state is laid out as ``(rows, 128)`` float slabs (rows a multiple of
the sublane tile), so the kernel is a pure VPU elementwise pass.  On
non-TPU backends it runs in interpret mode (exact semantics); the pure-jnp
:func:`primitive_update` is both the kernel body and the no-Pallas
fallback, so the two paths are bit-identical by construction.

Primitive codes extend ``repro.core.batch_sim``'s 0 noop / 1 work /
2 idle / 3 checkpoint with 4 = work *not* credited toward the regular
period (the device engine folds the NumPy engine's separate ``credit``
flag into the primitive code — one less lane array per iteration).

The module also hosts the *sampling step* of the device trace generator
(``trace_mode="device"``): a counter-based Threefry-2x32 stream cipher
(bit-identical to the NumPy reference in :mod:`repro.core.events`),
inverse-CDF inter-arrival transforms for the exponential / Weibull /
lognormal / uniform families, and :func:`stream_advance` — the fused
"draw the next event of a renewal stream" update.  Like the primitive
update it has a Pallas entry (:func:`masked_stream_advance`) whose body
is the pure-jnp function itself, so the two paths are bit-identical.
"""

from __future__ import annotations

from functools import partial
from math import gamma as _gamma

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.events import (
    _SM_GAMMA, _SM_MIX1, _SM_MIX2, _TF_PARITY, _TF_ROTATIONS, THREEFRY_ROUNDS,
    LAW_EXPONENTIAL, LAW_LOGNORMAL, LAW_UNIFORM, LAW_WEIBULL,
)
from ..core.simulator import _CLOCK_ULPS

__all__ = [
    "PRIM_NOOP",
    "PRIM_WORK",
    "PRIM_IDLE",
    "PRIM_CKPT",
    "PRIM_WORK_NC",
    "FLAG_FAULTED",
    "FLAG_OK",
    "FLAG_FIN",
    "FLAG_CKPT_OK",
    "FLAG_REG",
    "clock_tol",
    "primitive_update",
    "masked_primitive_update",
    "threefry2x32",
    "splitmix64",
    "stream_key",
    "counter_words",
    "uniform24",
    "counter_uniform",
    "counter_uniform2",
    "gap_transform",
    "gap_transform_indexed",
    "stream_advance",
    "masked_stream_advance",
    "cell_gather",
    "segment_cell_sums",
]

#: primitive kinds (0-3 shared with repro.core.batch_sim's _PR_* codes;
#: 4 is the device engine's uncredited-work variant of PRIM_WORK)
PRIM_NOOP, PRIM_WORK, PRIM_IDLE, PRIM_CKPT, PRIM_WORK_NC = 0, 1, 2, 3, 4

#: outcome bitfield
FLAG_FAULTED = 1  # a fault interrupted the primitive
FLAG_OK = 2  # primitive completed without fault
FLAG_FIN = 4  # the work segment finished the job
FLAG_CKPT_OK = 8  # a checkpoint committed (saved <- saved + unsaved)
FLAG_REG = 16  # ... and it was a *regular* (period-resetting) checkpoint


# repro-twin: repro.core.batch_sim.clock_tol
# repro-lint: jit-root
def clock_tol(t, eps: float):
    """Tolerance of an end-of-span check made at clock ``t``: the
    absolute ``eps`` or ``_CLOCK_ULPS`` ulps of ``t``'s float type,
    whichever is larger.

    In float64 ``eps`` decides (4 ulps of a clock stay below 1e-6 s up to
    ~10^9 s), so x64 runs compare against ``eps`` alone.  In float32 — the
    TPU's arithmetic — a clock near 10^6 s moves in 0.0625 s steps: a
    period or job end nearer than that to the clock cannot be reached by
    any step, and a lane waiting for it would spin forever.  A few ulps
    of slack end such a span at most ``_CLOCK_ULPS`` ulps early (under a
    second at paper makespans), far below Monte-Carlo resolution."""
    return jnp.maximum(eps, t * (_CLOCK_ULPS * jnp.finfo(t.dtype).eps))


# repro-lint: jit-root
def primitive_update(
    prim, cont, target, ckend, nf, t, saved, unsaved, pw, W, DR,
    *, eps: float, reg_cont: int, stream=None, gap=None,
):
    """One masked primitive execution; mirrors the NumPy engine's
    execute-one-primitive-per-lane block statement for statement.

    ``target`` must already be capped at job completion and ``ckend``
    fixed from the pre-fault-resolution clock (the caller replicates the
    scalar oracle's order of operations); ``nf`` is each lane's next
    pending fault after stale-fault resolution.  Returns
    ``(t, saved, unsaved, period_work, flags)``.

    Device trace mode fuses the generation step in: ``stream`` carries
    the strike cursor ``(key, ctr, tm, mean, horizon)`` (with ``nf ==
    tm``) and ``gap`` the static ``(kind, param)`` of the fault law; the
    consumed fault is then refilled by one counter draw where the
    primitive faulted, and the advanced ``(ctr, tm)`` pair is appended to
    the return tuple — sampling happens inside the (Pallas) hot step
    instead of a second kernel launch per iteration.
    """
    creditb = prim == PRIM_WORK
    workm = creditb | (prim == PRIM_WORK_NC)
    idlem = prim == PRIM_IDLE
    ckm = prim == PRIM_CKPT
    res = workm | idlem | ckm

    faulted = ((workm | idlem) & (nf <= target)) | (ckm & (nf < ckend))
    ok = res & ~faulted

    t1 = jnp.where(faulted, nf + DR, t)
    unsaved1 = jnp.where(faulted, 0.0, unsaved)
    pw1 = jnp.where(faulted, 0.0, pw)

    wok = workm & ok
    dt = target - t
    unsaved2 = jnp.where(wok, unsaved1 + dt, unsaved1)
    pw2 = jnp.where(wok & creditb, pw1 + dt, pw1)
    t2 = jnp.where(wok, target, t1)
    fin = wok & (saved + unsaved2 >= W - clock_tol(target, eps))

    iok = idlem & ok
    t3 = jnp.where(iok, target, t2)

    cok = ckm & ok
    t4 = jnp.where(cok, ckend, t3)
    saved2 = jnp.where(cok, saved + unsaved2, saved)
    unsaved3 = jnp.where(cok, 0.0, unsaved2)
    reg = cok & (cont == reg_cont)
    pw3 = jnp.where(reg, 0.0, pw2)

    flags = (
        faulted.astype(jnp.int32) * FLAG_FAULTED
        + ok.astype(jnp.int32) * FLAG_OK
        + fin.astype(jnp.int32) * FLAG_FIN
        + cok.astype(jnp.int32) * FLAG_CKPT_OK
        + reg.astype(jnp.int32) * FLAG_REG
    )
    if stream is None:
        return t4, saved2, unsaved3, pw3, flags
    if len(stream) == 5:
        skey, sctr, stm, smean, shorizon = stream
        slaw = slp = None
    else:  # law-multiplexed: per-lane law index + (s1, s2) shape slots
        skey, sctr, stm, smean, shorizon, slaw, s1, s2 = stream
        slp = (s1, s2)
    sctr, stm = stream_advance(
        faulted, sctr, stm, skey, smean, shorizon,
        kind=gap[0], param=gap[1], law=slaw, lp=slp,
    )
    return t4, saved2, unsaved3, pw3, flags, sctr, stm


# --------------------------------------------------------------------------- #
# Counter-based RNG sampling step (device trace generation)
# --------------------------------------------------------------------------- #
# repro-twin: repro.core.events.threefry2x32
# repro-lint: jit-root
def threefry2x32(k0, k1, c0, c1, rounds: int = THREEFRY_ROUNDS):
    """Threefry-2x32 over uint32 arrays; the jnp twin of
    :func:`repro.core.events.threefry2x32` (bit-identical by the shared
    rotation/key-schedule constants; pinned by a known-answer test)."""
    k0 = jnp.asarray(k0, jnp.uint32)
    k1 = jnp.asarray(k1, jnp.uint32)
    x0 = jnp.asarray(c0, jnp.uint32)
    x1 = jnp.asarray(c1, jnp.uint32)
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(_TF_PARITY))
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for i in range(rounds):
        r = _TF_ROTATIONS[(i // 4) % 2][i % 4]
        x0 = x0 + x1
        x1 = (x1 << r) | (x1 >> (32 - r))
        x1 = x1 ^ x0
        if i % 4 == 3:
            s = i // 4 + 1
            x0 = x0 + ks[s % 3]
            x1 = x1 + ks[(s + 1) % 3] + jnp.uint32(s)
    return x0, x1


# repro-twin: repro.core.events.uniform24
# repro-lint: jit-root
def uniform24(bits, dtype):
    """uint32 words -> uniforms in the open interval (0, 1) (top 24 bits,
    half-ulp centered); see the NumPy twin in ``core.events``.  The
    shifted word is below 2^24, so it goes through int32 on its way to
    float: exact, and the only integer-to-float cast Mosaic lowers."""
    return (
        (bits >> 8).astype(jnp.int32).astype(dtype) + jnp.asarray(0.5, dtype)
    ) * jnp.asarray(2.0**-24, dtype)


# repro-twin: repro.core.events.splitmix64
# repro-lint: jit-root
def splitmix64(key64, ctr):
    """Counter-indexed SplitMix64 draw (jnp twin of
    ``core.events.splitmix64``): 64 output bits as (high, low) uint32
    words.  The per-event hot path — ~10 integer ops against the ~80 of a
    full Threefry evaluation, with BigCrush-level stream quality."""
    z = jnp.asarray(key64, jnp.uint64) + (
        ctr.astype(jnp.uint64) + jnp.uint64(1)
    ) * jnp.uint64(_SM_GAMMA)
    z = (z ^ (z >> 30)) * jnp.uint64(_SM_MIX1)
    z = (z ^ (z >> 27)) * jnp.uint64(_SM_MIX2)
    z = z ^ (z >> 31)
    return (z >> 32).astype(jnp.uint32), z.astype(jnp.uint32)


# repro-lint: jit-root
def stream_key(k0, k1, *, x64: bool):
    """Pack a Threefry subkey pair into the per-draw key representation
    of an engine running at precision ``x64``: a single uint64
    (SplitMix64 draws) in float64 — matching
    :meth:`TraceSpec.materialize` — or the uint32 pair itself (Threefry
    draws) in float32, the TPU's arithmetic, whose kernels have no
    uint64.  Keyed on the engine's precision, never on the process-wide
    x64 flag."""
    if x64:
        return ((k0.astype(jnp.uint64) << 32) | k1.astype(jnp.uint64),)
    return (k0, k1)


# repro-lint: jit-root
def counter_words(key, ctr):
    """Output words of draw ``ctr`` for a :func:`stream_key` key."""
    if len(key) == 1:
        return splitmix64(key[0], ctr)
    return threefry2x32(key[0], key[1], ctr.astype(jnp.uint32), jnp.uint32(0))


# repro-lint: jit-root
def counter_uniform(key, ctr, dtype):
    """Draw ``ctr``'s uniform from the stream keyed ``key``."""
    x0, _ = counter_words(key, ctr)
    return uniform24(x0, dtype)


# repro-lint: jit-root
def counter_uniform2(key, ctr, dtype):
    """Both uniforms of one draw (e.g. the TP coin stream: word 0 is the
    predicted coin, word 1 the window-offset fraction)."""
    x0, x1 = counter_words(key, ctr)
    return uniform24(x0, dtype), uniform24(x1, dtype)


# repro-twin: repro.core.events.gap_transform_np
# repro-lint: jit-root
def gap_transform(kind: str, param: float, mean, x0, x1, dtype):
    """Inverse-CDF inter-arrival transform of one counter draw (jnp twin
    of ``core.events.gap_transform_np``; ``kind`` is compile-time static).
    Only the lognormal family consumes the second cipher word (Box–Muller
    phase).  Clamped to the host generator's ``1e-9`` zero-gap guard."""
    u = uniform24(x0, dtype)
    if kind == "exponential":
        g = -jnp.log1p(-u) * mean
    elif kind == "weibull":
        scale = 1.0 / _gamma(1.0 + 1.0 / param)
        g = (mean * scale) * (-jnp.log1p(-u)) ** (1.0 / param)
    elif kind == "lognormal":
        z = jnp.sqrt(-2.0 * jnp.log(u)) * jnp.cos(
            jnp.asarray(2.0 * 3.141592653589793, dtype) * uniform24(x1, dtype)
        )
        g = jnp.exp(jnp.log(mean) - 0.5 * param * param + param * z)
    elif kind == "uniform":
        g = 2.0 * mean * u
    else:  # pragma: no cover - validated at TraceSpec construction
        raise ValueError(f"unsupported gap kind {kind!r}")
    return jnp.maximum(g, 1e-9)


# repro-twin: repro.core.events.gap_transform_indexed_np
# repro-lint: jit-root
def gap_transform_indexed(law, s1, s2, mean, x0, x1, dtype):
    """Law-multiplexed inverse-CDF transform: the branchless select twin
    of :func:`gap_transform` for mixed-law cell tables.

    ``law`` is the per-lane int32 law index (``core.events.LAW_*``) and
    ``(s1, s2)`` the pre-folded shape slots of the unified 4-slot
    parameter row (``core.events.law_table``): Weibull ``s1 = 1/Γ(1+1/k)``,
    ``s2 = 1/k``; lognormal ``s1 = σ``, ``s2 = σ²/2``.  Every family's
    expression is evaluated (a pure VPU elementwise pass) and one
    ``where`` chain selects per lane; each branch is written so that with
    the slots pinned to a single family it folds to the *same* XLA ops as
    the compile-time-specialized path — the per-cell bit-identity the
    fused mixed-law dispatch is gated on."""
    u = uniform24(x0, dtype)
    nlog = -jnp.log1p(-u)
    g_exp = nlog * mean
    # mirror the compiler's static-exponent pow strength reductions
    # (x ** 2.0 -> x * x, x ** 0.5 -> sqrt) so the data-driven exponent
    # reproduces the specialized path's bits for those shapes too
    p = nlog ** s2
    p = jnp.where(s2 == 2.0, nlog * nlog, p)
    p = jnp.where(s2 == 0.5, jnp.sqrt(nlog), p)
    g_wei = (mean * s1) * p
    z = jnp.sqrt(-2.0 * jnp.log(u)) * jnp.cos(
        jnp.asarray(2.0 * 3.141592653589793, dtype) * uniform24(x1, dtype)
    )
    g_log = jnp.exp(jnp.log(mean) - s2 + s1 * z)
    g_uni = 2.0 * mean * u
    g = jnp.where(
        law == LAW_WEIBULL, g_wei,
        jnp.where(
            law == LAW_LOGNORMAL, g_log,
            jnp.where(law == LAW_UNIFORM, g_uni, g_exp),
        ),
    )
    return jnp.maximum(g, 1e-9)


# repro-lint: jit-root
def stream_advance(
    mask, ctr, tm, key, mean, horizon, *, kind, param, law=None, lp=None,
):
    """Advance a renewal-stream cursor by one event where ``mask``.

    Draws gap ``ctr + 1`` from the counter stream, accumulates the event
    date, and retires the stream (``+inf``) once it crosses the lane's
    generation horizon — the O(1)-state replacement for a materialized,
    sentinel-padded event row.  Lanes outside ``mask`` are untouched, and
    a draw is a pure function of ``(key, counter)``, so cursor replays
    (e.g. the strike cursor re-walking the lookahead cursor's fault
    stream) observe bit-identical dates.

    ``kind="indexed"`` selects the law-multiplexed transform: ``law`` is
    the per-lane int32 law index and ``lp`` the ``(s1, s2)`` shape-slot
    pair (``param`` is ignored)."""
    c2 = ctr + 1
    x0, x1 = counter_words(key, c2)
    if kind == "indexed":
        g = gap_transform_indexed(law, lp[0], lp[1], mean, x0, x1, tm.dtype)
    else:
        g = gap_transform(kind, param, mean, x0, x1, tm.dtype)
    t2 = tm + g
    t2 = jnp.where(t2 > horizon, jnp.asarray(jnp.inf, tm.dtype), t2)
    return jnp.where(mask, c2, ctr), jnp.where(mask, t2, tm)


# --------------------------------------------------------------------------- #
# Cell multiplexing (fused experiment sweeps)
# --------------------------------------------------------------------------- #
# repro-lint: jit-root
def cell_gather(consts: dict, cidx, keys) -> dict:
    """Broadcast per-cell table rows to per-lane arrays.

    The fused sweep ships each engine parameter as a compact ``(n_cells,)``
    table plus one ``(lanes,)`` int32 ``cidx``; this gather — one fused
    ``take`` per parameter at the top of the jitted program — recovers the
    per-lane layout the lane machine runs on, so lanes from many
    experiment cells interleave freely across chunks and shards.  Returns
    a copy of ``consts`` with every key in ``keys`` gathered (keys absent
    from ``consts`` are skipped: trace-mode-specific tables)."""
    out = dict(consts)
    for k in keys:
        # keys is a static tuple of table names, not traced data
        if k in consts:  # repro-lint: disable=tracer-branch
            out[k] = jnp.take(consts[k], cidx, axis=0)
    return out


# repro-lint: jit-root
def segment_cell_sums(values, cidx, num_cells: int):
    """Per-cell sums of per-lane columns in one segment reduction.

    ``values`` is a sequence of ``(L,)`` arrays (clock, waste, event
    counters, ...); the result is a ``(num_cells, len(values))`` float
    matrix whose row ``c`` sums the lanes with ``cidx == c`` — the
    device-side reduction of per-cell Monte-Carlo moments, so a fused
    sweep can fetch O(cells) statistics instead of O(lanes) results.
    Counters are exact in f64 (and up to 2^24 lanes in the f32/TPU
    path); callers route padding lanes to a sacrificial trailing cell
    row and drop it host-side."""
    import jax

    fdt = values[0].dtype
    x = jnp.stack([v.astype(fdt) for v in values], axis=-1)
    return jax.ops.segment_sum(x, cidx, num_segments=num_cells)


def _advance_kernel(*refs, kind: str, param: float, nkey: int):
    mask_ref, ctr_ref, tm_ref = refs[:3]
    key = tuple(r[...] for r in refs[3:3 + nkey])
    if kind == "indexed":
        (mean_ref, horizon_ref, law_ref, s1_ref, s2_ref,
         ctr_out, tm_out) = refs[3 + nkey:]
        law, lp = law_ref[...], (s1_ref[...], s2_ref[...])
    else:
        mean_ref, horizon_ref, ctr_out, tm_out = refs[3 + nkey:]
        law = lp = None
    ctr, tm = stream_advance(
        mask_ref[...] != 0, ctr_ref[...], tm_ref[...], key,
        mean_ref[...], horizon_ref[...], kind=kind, param=param,
        law=law, lp=lp,
    )
    ctr_out[...] = ctr
    tm_out[...] = tm


def masked_stream_advance(
    mask, ctr, tm, key, mean, horizon, *, kind: str, param: float,
    law=None, lp=None, interpret: bool | None = None, tile: int = 8,
):
    """Pallas entry of :func:`stream_advance` over flat ``(L,)`` lanes
    (L % 128 == 0), same layout/tiling contract as
    :func:`masked_primitive_update`; the kernel body *is* the jnp
    function, so both paths are bit-identical.  ``kind="indexed"`` ships
    the per-lane ``law`` index and ``lp = (s1, s2)`` slot arrays as three
    extra kernel inputs."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    L = tm.shape[0]
    if L % 128:
        raise ValueError(f"lane count {L} not a multiple of 128")
    rows = L // 128
    if interpret:
        tile = rows
    tile = max(1, min(tile, rows))
    while rows % tile:
        tile //= 2
    fdt = tm.dtype

    def as2d(x, dtype=None):
        # dtype=None deliberately preserves the key words' uint dtype
        x = jnp.asarray(x) if dtype is None else jnp.asarray(x, dtype)  # repro-lint: disable=kernel-dtype
        return x.reshape(rows, 128)

    ins = [
        as2d(mask, jnp.int32),
        as2d(ctr, jnp.int32),
        as2d(tm, fdt),
        *[as2d(k) for k in key],
        as2d(mean, fdt),
        as2d(horizon, fdt),
    ]
    if kind == "indexed":
        ins += [as2d(law, jnp.int32), as2d(lp[0], fdt), as2d(lp[1], fdt)]
    spec = pl.BlockSpec((tile, 128), lambda i: (i, 0))
    out_shape = [
        jax.ShapeDtypeStruct((rows, 128), jnp.int32),
        jax.ShapeDtypeStruct((rows, 128), fdt),
    ]
    outs = pl.pallas_call(
        partial(_advance_kernel, kind=kind, param=param, nkey=len(key)),
        grid=(rows // tile,),
        in_specs=[spec] * len(ins),
        out_specs=[spec] * len(out_shape),
        out_shape=out_shape,
        # the cursor pair is loop-carried state: update it in place
        input_output_aliases={1: 0, 2: 1},
        interpret=interpret,
    )(*ins)
    return tuple(o.reshape(L) for o in outs)


def _step_kernel(
    prim_ref, cont_ref, target_ref, ckend_ref, nf_ref,
    t_ref, saved_ref, unsaved_ref, pw_ref, w_ref, dr_ref,
    t_out, saved_out, unsaved_out, pw_out, flags_out,
    *, eps: float, reg_cont: int,
):
    t, saved, unsaved, pw, flags = primitive_update(
        prim_ref[...], cont_ref[...], target_ref[...],
        ckend_ref[...], nf_ref[...], t_ref[...], saved_ref[...],
        unsaved_ref[...], pw_ref[...], w_ref[...], dr_ref[...],
        eps=eps, reg_cont=reg_cont,
    )
    t_out[...] = t
    saved_out[...] = saved
    unsaved_out[...] = unsaved
    pw_out[...] = pw
    flags_out[...] = flags


def _step_gen_kernel(*refs, eps: float, reg_cont: int, gap, nkey: int):
    # device trace mode: the strike time IS nf, so the stream tuple
    # reuses nf_ref and the consumed fault is refilled in-kernel
    (prim_ref, cont_ref, target_ref, ckend_ref, nf_ref,
     t_ref, saved_ref, unsaved_ref, pw_ref, w_ref, dr_ref) = refs[:11]
    key = tuple(r[...] for r in refs[11:11 + nkey])
    sctr_ref, mean_ref, horizon_ref = refs[11 + nkey:14 + nkey]
    stream = (key, sctr_ref[...], nf_ref[...],
              mean_ref[...], horizon_ref[...])
    if gap[0] == "indexed":  # + per-lane law index and (s1, s2) slots
        law_ref, s1_ref, s2_ref = refs[14 + nkey:17 + nkey]
        stream = stream + (law_ref[...], s1_ref[...], s2_ref[...])
        rest = refs[17 + nkey:]
    else:
        rest = refs[14 + nkey:]
    (t_out, saved_out, unsaved_out, pw_out, flags_out,
     sctr_out, stm_out) = rest
    t, saved, unsaved, pw, flags, sctr, stm = primitive_update(
        prim_ref[...], cont_ref[...], target_ref[...],
        ckend_ref[...], nf_ref[...], t_ref[...], saved_ref[...],
        unsaved_ref[...], pw_ref[...], w_ref[...], dr_ref[...],
        eps=eps, reg_cont=reg_cont, stream=stream, gap=gap,
    )
    t_out[...] = t
    saved_out[...] = saved
    unsaved_out[...] = unsaved
    pw_out[...] = pw
    flags_out[...] = flags
    sctr_out[...] = sctr
    stm_out[...] = stm


def masked_primitive_update(
    prim, cont, target, ckend, nf, t, saved, unsaved, pw, W, DR,
    *, eps: float, reg_cont: int, interpret: bool | None = None,
    tile: int = 8, stream=None, gap=None,
):
    """Pallas entry point over flat ``(L,)`` lane vectors, L % 128 == 0.

    The lane axis is viewed as ``(L // 128, 128)`` and tiled ``tile`` rows
    per grid step (8 rows = the f32 sublane tile).  ``interpret`` defaults
    to True off-TPU (the repo-wide kernel idiom, see kernels/ops.py).

    With ``stream``/``gap`` (device trace mode; ``stream[3]`` must be the
    same array as ``nf``) the sampling step is fused into the kernel and
    the advanced strike cursor is appended to the outputs.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    L = t.shape[0]
    if L % 128:
        raise ValueError(f"lane count {L} not a multiple of 128")
    rows = L // 128
    if interpret:
        tile = rows  # no VMEM budget to respect: one grid step, no slicing
    tile = max(1, min(tile, rows))
    while rows % tile:
        tile //= 2

    fdt = t.dtype

    def as2d(x, dtype):
        return jnp.asarray(x, dtype).reshape(rows, 128)

    ins = [
        as2d(prim, jnp.int32),
        as2d(cont, jnp.int32),
        as2d(target, fdt),
        as2d(ckend, fdt),
        as2d(nf, fdt),
        as2d(t, fdt),
        as2d(saved, fdt),
        as2d(unsaved, fdt),
        as2d(pw, fdt),
        as2d(W, fdt),
        as2d(DR, fdt),
    ]
    spec = pl.BlockSpec((tile, 128), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((rows, 128), fdt)] * 4 + [
        jax.ShapeDtypeStruct((rows, 128), jnp.int32)
    ]
    # the float lane-state slabs (t/saved/unsaved/pw, inputs 5-8) are
    # loop-carried intermediates: alias them onto the corresponding
    # outputs so the step updates state in place instead of streaming
    # four fresh (rows, 128) buffers per iteration
    aliases = {5: 0, 6: 1, 7: 2, 8: 3}
    if stream is None:
        kernel = partial(_step_kernel, eps=eps, reg_cont=reg_cont)
    else:
        skey, sctr, _, smean, shorizon = stream[:5]
        ins += [
            # dtype-preserving on purpose: uint64 (SplitMix) or uint32 pair
            *[jnp.asarray(k).reshape(rows, 128) for k in skey],  # repro-lint: disable=kernel-dtype
            as2d(sctr, jnp.int32),
            as2d(smean, fdt),
            as2d(shorizon, fdt),
        ]
        if len(stream) == 8:  # law-multiplexed: law index + (s1, s2)
            ins += [
                as2d(stream[5], jnp.int32),
                as2d(stream[6], fdt),
                as2d(stream[7], fdt),
            ]
        out_shape += [
            jax.ShapeDtypeStruct((rows, 128), jnp.int32),
            jax.ShapeDtypeStruct((rows, 128), fdt),
        ]
        aliases[11 + len(skey)] = 5  # the strike counter is loop-carried
        kernel = partial(
            _step_gen_kernel, eps=eps, reg_cont=reg_cont, gap=gap,
            nkey=len(skey),
        )
    outs = pl.pallas_call(
        kernel,
        grid=(rows // tile,),
        in_specs=[spec] * len(ins),
        out_specs=[spec] * len(out_shape),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(*ins)
    return tuple(o.reshape(L) for o in outs)
