"""JAX/Pallas device simulation engine (``engine="jax"``).

Design note — the device lane semantics (mirrors ``batch_sim.py``)
==================================================================

This module re-expresses the NumPy lane-per-trace engine
(:mod:`repro.core.batch_sim`) as a *fixed-shape masked computation* that
jit-compiles to a single XLA while-loop, unlocking Monte-Carlo campaigns
(10^4-10^5 traces) the interpreter-bound engines cannot reach:

* **Stacked lane-state pytree** — every per-lane quantity of the NumPy
  engine (clock ``t``, ``saved``/``unsaved`` work, fault/prediction
  cursors ``fi``/``pi``, phase code, event counters, the mutable
  fault-cancellation mask) becomes one device array of shape ``(L,)``
  (``(L, F)`` for the cancellation mask) carried through
  ``lax.while_loop``.
* **Masked phase decisions** — the NumPy engine's boolean-index writes
  (``prim[ck] = ...``) become ``jnp.where`` merges keyed on the phase
  codes captured at the top of the iteration; every lane advances by
  exactly one primitive per outer iteration, exactly as in NumPy.
* **No live-lane repacking** — the NumPy engine compacts finished lanes
  away; here a finished lane goes *inert* (phase ``DONE`` masks every
  update) because fixed shapes are what lets XLA fuse each iteration
  into a handful of kernels.  Host-side ``chunk`` scheduling recovers
  the lost-work bound (and the memory bound) for very large grids.
* **Data-dependent inner loops** — two nested ``lax.while_loop``s per
  iteration, whose bodies advance *all* affected lanes per pass.  The
  cascade of faults that strike during downtime is one.  The other is
  the *prediction walk*, one flat loop after the pop: a lane seeks at
  most one prediction stream at a time (the next visible true positive
  or the next visible false prediction), takes one draw of it per pass,
  and once it seeks nothing re-checks whether its merged head's action
  point has passed, and if so seeks that head's stream.  So the pop's
  refill and the next iteration's skips share one loop, whose trip count
  is the draws of its busiest lane (``LAST_TIMINGS["walk_passes"]``).
* **Pallas hot step** — the masked primitive execution (fault check +
  work/idle/checkpoint update) is the dense elementwise block run every
  iteration; it executes as a Pallas kernel
  (:mod:`repro.kernels.sim_step`), interpret-mode off-TPU, with a
  pure-jnp fallback (``use_pallas=False``) that shares the same body.
* **Lane-sharded multi-device dispatch** — lanes are mutually
  independent, so ``devices=`` shards each chunk's lane axis across a
  1-D ``("lanes",)`` mesh via ``shard_map`` (the ``jax.pmap`` runner it
  replaces kept a leading device axis host-side; ``devices=``/``mesh=``
  semantics are unchanged); per-lane results are identical to the
  single-device path for any device count (each lane executes the same
  primitive sequence regardless of which lanes co-reside), and each
  device's while-loop exits as soon as its own shard finishes.  Cell
  tables ride along replicated; ``collect="stats"`` reduces per-cell
  sums with one ``psum`` at chunk end into a *donated on-device
  accumulator*, so per-lane slabs never cross the host boundary — the
  host fetches O(cells) exactly once per call.
* **Async double-buffered chunk pipeline** — chunk packing is pure host
  NumPy and dispatch is JAX-async, so the scheduler packs and ships
  chunk ``k+1`` while chunk ``k`` executes, then fetches results one
  chunk behind the dispatch front (``copy_to_host_async`` first, so the
  D2H copies overlap too).  State buffers are donated to the executable.
* **Two-level compilation cache** — an in-process runner registry keyed
  on the (pallas, precision, migration, device-set) specialization, plus
  JAX's persistent compilation cache (:func:`enable_compilation_cache`:
  ``JAX_COMPILATION_CACHE_DIR`` or ``.jax_cache/`` in the checkout) so
  repeated sweep *processes* skip XLA recompiles of the same chunk
  shapes entirely.

Because this engine and the NumPy engine execute the same primitive
sequence in the same order, their makespans agree to float rounding when
run in float64 (``precision="x64"``, the default off-TPU; on a TPU the
engine runs float32 with Threefry-x32 draws, and its end-of-span checks
allow a few ulps of the clock, see ``sim_step.clock_tol``).  Trust filtering happens host-side through
the NumPy engine's own filter, so the deterministic trust settings
``q in {0, 1}`` used by all paper strategies are trace-identical across
the scalar, NumPy-batch, and JAX engines.

Device trace generation (``trace_mode="device"`` / :class:`TraceSpec`)
======================================================================

Passing a :class:`~repro.core.events.TraceSpec` instead of materialized
:class:`~repro.core.events.BatchTraces` moves event generation *inside*
the engine: no host sampling, no sentinel-padded ``(lanes, events)``
slabs, no ``(events, lanes)`` transpose, no host->device event copy —
chunk packing ships O(lanes) scalars and chunking exists purely for
compilation-shape reuse, so multi-million-lane campaigns fit trivially.

**RNG stream layout** (the reproducibility contract; NumPy reference in
:meth:`TraceSpec.materialize`):

* lane ``i`` owns a 64-bit stream id ``spec.stream[i]`` — a *global*
  lane identity that travels with the lane through chunking, sharding
  and ``take``/``tile``, which is what makes results invariant to chunk
  size and device count for a fixed ``(seed, stream)`` assignment.
* per-(lane, kind) subkeys are derived once per chunk:
  ``threefry2x32(seed_words, (stream_lo, stream_hi << 4 | kind))`` with
  the five kinds of :mod:`repro.core.events` (``STREAM_FAULT_GAP``,
  ``STREAM_TP_COIN`` — word 0 the predicted coin, word 1 the window
  offset — ``STREAM_FP_GAP``, ``STREAM_TP_TRUST``, ``STREAM_FP_TRUST``).
* draw ``n`` of a stream is ``SplitMix64(subkey_as_u64, n)`` (x64; the
  x32/TPU fallback is ``threefry2x32(subkey, (n, 0))``) — counter
  indexed, never sequential, so cursors can replay a stream (the strike
  cursor re-walks the lookahead cursor's fault stream) and strategy-side
  draws (trust coins) never perturb trace-side draws.

**O(1) lane cursors** replace the per-lane event rows:

* *strike cursor* ``(sf_ctr, sf_time)`` — the next fault to hit the
  node; refilled by one counter draw when a fault resolves (fused into
  the Pallas hot step) or goes stale during downtime.
* *lookahead cursor* ``(la_ctr, la_time)`` + *pending-TP buffer*
  ``(tp_t0, tp_ft, tp_ctr)`` — the fault stream is walked ahead of the
  strike cursor to find the *visible* true-positive predictions (recall
  coin, then trust coin for fractional ``q``); a window's position comes
  from the offset stream.  The buffer holds each lane's pending TPs in
  window-start order, ties in fault order — the order of the host
  generator's stable sort.  The walk inserts every visible TP it draws
  and walks on until the next fault's date, less one window, reaches the
  head's window start: no later fault can then open its window first.
  A call with no window (``has_window`` false) keeps one slot, where
  the first visible TP is final at once.  With windows a call keeps
  :data:`WINDOW_TP_SLOTS`; a lane that would need more is reported
  (``LAST_TIMINGS["tp_overflow"]``, and the call raises), never served
  out of order.
* *false-prediction cursor* ``(fp_ctr, fp_time)`` — an independent
  renewal stream at the Section 2.3 false-prediction rate.
* the merged prediction head is ``min(tp_t0[0], fp_time)`` (ties to the
  TP, matching the host generator's stable sort), so predictions are
  consumed in the host path's order, with or without windows: the two
  trace modes run the same event sequence per lane.
* *migration cancel slots* ``(ep_fctr, cancel_ctr[3])`` — the
  vacated-node fault is cancelled by counter index instead of an
  ``(L, F)`` mask scan.  With windows TPs are popped in window order, so
  cancellations are set out of fault order; that is sound because each
  slot is retired on its own (the strike cursor visits counters
  monotonically and drops every slot it has passed) and a new
  cancellation takes any free slot, so the slots hold exactly the
  pending cancellations whatever order they were set in.  A fourth
  *simultaneously pending* cancellation (four migration episodes begun
  whose predicted faults have not yet come) is dropped — beyond
  pathological at exact dates; with windows as wide as the mean fault
  gap it is not, so a call with windows reports it as an overflow.

Streams retire at the lane's generation horizon (date ``+inf``), exactly
like the host generator's ``(0, horizon]`` clipping.  Equivalence with
the host-generated path is statistical (same laws, different draws);
:meth:`TraceSpec.materialize` replays the identical streams on the host,
and the NumPy engine on that replay agrees with this engine per lane to
float rounding (trust ``q`` in {0, 1}), windows included.
"""

from __future__ import annotations

import os
import warnings
from collections import OrderedDict
from dataclasses import dataclass
from functools import partial
from typing import Optional, Sequence, Union

import numpy as np

from . import batch_sim as B
from . import events as E
from .batch_sim import BatchResult, pad_lane_axis
from .events import BatchTraces, TraceSpec, pad_sentinel
from .simulator import Strategy, _EPS
from .spans import span
from .waste import Platform

__all__ = [
    "simulate_batch_jax",
    "CellSums",
    "default_chunk_lanes",
    "device_interarrival_samples",
    "enable_compilation_cache",
    "LAST_TIMINGS",
    "LANE_TILE",
    "SHARD_TILE",
]

#: what the most recent :func:`simulate_batch_jax` call did:
#: {"trace_mode", "pack_s", "dispatch_s", "fetch_s", "n_chunks",
#: "loop_iters", "walk_passes", "tp_overtakes", "tp_pending_peak",
#: "tp_overflow"}, plus what the call resolved: ``precision``
#: ("x64"/"x32") and ``pallas`` ("compiled", "interpret" or "off").
#: The seconds are the host durations of the call's spans
#: (:mod:`repro.core.spans`): ``pack_s`` the ``repro.engine.pack`` spans,
#: host NumPy packing (events for the host trace mode, O(lanes) scalars
#: for device mode); ``dispatch_s`` the ``repro.engine.dispatch`` spans,
#: device_put + async launch; ``fetch_s`` the ``repro.engine.wait`` and
#: ``repro.engine.fetch`` spans, the device wait + D2H copies.
#: ``loop_iters`` is an int64 ``(n_chunks, n_devices)`` array: the outer
#: while loop's iterations for each chunk on each device, fetched with
#: the results.  ``walk_passes`` has the same shape: the passes of the
#: prediction walk (device trace mode; 0 for host traces), summed over
#: the chunk's outer iterations (the priming walk before the loop is not
#: counted).  The pending-TP buffer's counts have the same shape and are
#: 0 in a call with no window (one slot, nothing to order):
#: ``tp_overtakes`` the TPs that entered a lane's buffer ahead of a TP
#: already pending there (a later fault's window opening first),
#: ``tp_pending_peak`` the most TPs any lane held pending at once, and
#: ``tp_overflow`` the lanes that needed more than
#: :data:`WINDOW_TP_SLOTS`, or more than the three migration cancel
#: slots (the call then raises).  Benchmarks read them
#: to attribute end-to-end time.
LAST_TIMINGS: dict = {}

#: lane-count granularity: 8 f32 sublanes x 128 lanes, the Pallas tile
LANE_TILE = 1024

#: pending-TP slots per lane in a call with prediction windows (a call
#: without keeps the one slot).  Sized from the tail of the pending count
#: at the Section 5 window cell's worst point (2^19 nodes, MTBF 7,524 s,
#: r = 0.85, I = 6,000 s, r I / mu = 0.68): over 2.55e7 TPs replayed on
#: the host with this lookahead rule, a TP found 3 or more pending with
#: it 25 % of the time, 5 or more 0.43 %, 7 or more 2.4e-5, 8 or more
#: 1.5e-6 and 9 or more 3.9e-8, the tail falling 12-fold a slot and
#: steepening; so 17 pending (an overflow) is below 1e-15 a TP, against
#: the ~1e10 TPs a benchmark check draws there.  An overflow is reported
#: (``LAST_TIMINGS["tp_overflow"]`` and a RuntimeError), never dropped.
WINDOW_TP_SLOTS = 16

#: counts a chunk reports per device, in order: outer-loop iterations,
#: walk passes, and (calls with windows) TPs that overtook a pending one,
#: the most TPs a lane held pending, lanes whose buffer overflowed
_N_COUNTS = 5

#: per-device lane granularity of the sharded dispatch (the Pallas row
#: width): small enough that 8-way sharding of a cache-sized CPU chunk
#: still leaves every device a few tiles, large enough to stay tiled
SHARD_TILE = 128

#: where compiled executables persist when ``JAX_COMPILATION_CACHE_DIR``
#: is not set: one fixed directory in the checkout (a cache only hits
#: from a path that does not move between processes)
DEFAULT_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache"
))

#: default chunks: bound device-resident lanes so 100k-lane grids don't
#: OOM (and bound the inert-lane overhead of the no-repacking design).
#: On CPU a cache-sized chunk beats one giant batch; accelerators want
#: large chunks to stay utilization-bound.  Device trace mode carries no
#: event slabs — its per-lane state is ~50x smaller — so the cache-sized
#: CPU chunk holds twice the lanes (measured optimum at 40960 lanes).
_DEFAULT_CHUNK_CPU = 5120
_DEFAULT_CHUNK_CPU_SPEC = 10240
_DEFAULT_CHUNK_DEV = 16384


def default_chunk_lanes(
    devices=None, mesh=None, trace_mode: str = "device"
) -> int:
    """The lane count ``chunk="auto"`` resolves to for a device set.

    Public so callers that own the chunk loop themselves — the resumable
    campaign runner dispatches one engine call per campaign chunk so it
    can snapshot between them — pick the same measured-optimal chunk as
    the engine's internal pipeline."""
    devs = _resolve_devices(devices, mesh)
    n_dev = len(devs)
    if devs[0].platform == "cpu":
        base = (
            _DEFAULT_CHUNK_CPU_SPEC
            if trace_mode == "device"
            else _DEFAULT_CHUNK_CPU
        )
        return base * min(n_dev, 2)
    return _DEFAULT_CHUNK_DEV * n_dev


def _jit_run(consts, state, *, use_pallas, interpret, max_iters, eps,
             has_migration, has_two_level=False, has_silent=False,
             has_window=False, tp_slots=1, gen=None, gathered=(), n_seg=0):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..kernels.sim_step import (
        FLAG_CKPT_OK, FLAG_FAULTED, FLAG_FIN, FLAG_OK, FLAG_REG,
        PRIM_WORK_NC, cell_gather, clock_tol, counter_uniform,
        counter_uniform2, masked_primitive_update, primitive_update,
        segment_cell_sums, stream_advance, stream_key, threefry2x32,
    )

    # cell multiplexing (fused sweeps): per-cell parameter tables are
    # broadcast to per-lane arrays by the lane -> cell index once per
    # chunk; everything downstream runs the ordinary per-lane program
    cidx = consts.get("cidx")
    if gathered:
        consts = cell_gather(consts, cidx, gathered)

    CONT2PH = jnp.asarray(B._CONT2PH, jnp.int32)
    MODE2PH = jnp.asarray(B._MODE2PH, jnp.int32)

    device_gen = gen is not None
    if device_gen:
        F = P0 = Pft = frows = None
    else:
        # event arrays are (events, lanes): cursor gathers a[cursor[l], l]
        # then touch a handful of contiguous (L,)-rows (lanes advance
        # through their traces roughly in step), not one element per 2 KB
        # row of the (lanes, events) layout — the difference between L1
        # hits and L cache misses per gather, several times per iteration
        F, P0, Pft = consts["F"], consts["P0"], consts["Pft"]
        frows = jnp.arange(F.shape[0], dtype=jnp.int32)[:, None]
    W, C, DR = consts["W"], consts["C"], consts["DR"]
    T_R, T_P, mode = consts["T_R"], consts["T_P"], consts["mode"]
    horizon, window = consts["horizon"], consts["window"]
    wpp, lead_act = consts["wpp"], consts["lead_act"]
    tp_eff_default = consts["tp_eff_default"]
    # two-level / silent-error phase families (specialized out of every
    # other sweep's compiled step, exactly like has_migration)
    tl_m = (mode == B._M_TWO_LEVEL) if has_two_level else None
    sil_m = (mode == B._M_SILENT) if has_silent else None
    if has_two_level:
        C2, DR2 = consts["C2"], consts["DR2"]
        fmem, rho = consts["fmem"], consts["rho"]
        Ftier = None if gen is not None else consts["Ftier"]
    if has_silent:
        V, kv = consts["V"], consts["kv"]

    def take(a, idx):
        return jnp.take_along_axis(a, idx[None, :], axis=0)[0]

    if device_gen:
        # ---- counter-based generator closures (see module docstring) -- #
        f_kind, f_param, fp_kind, fp_param, frac_q = gen
        fdt = horizon.dtype
        mtbf, fp_mean = consts["mtbf"], consts["fp_mean"]
        recall, q_eff = consts["recall"], consts["q_eff"]
        inf = jnp.asarray(jnp.inf, fdt)
        nan = jnp.asarray(jnp.nan, fdt)

        def subkey(kind):
            # Threefry-derived per-(lane, kind) subkeys, once per chunk;
            # packed by stream_key into the per-draw representation
            # (uint64 SplitMix key on x64, the pair itself on x32)
            return stream_key(*threefry2x32(
                consts["s0"], consts["s1"], consts["sid_lo"],
                (consts["sid_hi"] << 4) | jnp.uint32(kind),
            ), x64=fdt == np.float64)

        fg_key = subkey(E.STREAM_FAULT_GAP)
        tc_key = subkey(E.STREAM_TP_COIN)
        fp_key = subkey(E.STREAM_FP_GAP)
        if has_two_level:
            # recovery-tier coin of fault i: counter i of the tier stream
            # (the NumPy twin lives in TraceSpec.materialize)
            tier_key = subkey(E.STREAM_TIER)
        if frac_q:
            tt_key = subkey(E.STREAM_TP_TRUST)
            ft_key = subkey(E.STREAM_FP_TRUST)

        # law-multiplexed sampling: when the chunk mixes failure laws the
        # static (kind, param) specialization is replaced by per-lane law
        # indices + slot parameters gathered from the cell tables, and the
        # gap transform becomes a branchless select (gap_transform_indexed)
        f_law = f_lp = fp_law = fp_lp = None
        if f_kind == "indexed":
            f_law = consts["fault_law"]
            f_lp = (consts["fault_s1"], consts["fault_s2"])
        if fp_kind == "indexed":
            fp_law = consts["fp_law"]
            fp_lp = (consts["fp_s1"], consts["fp_s2"])

        def adv_fault(m, ctr, tm):
            return stream_advance(
                m, ctr, tm, fg_key, mtbf, horizon,
                kind=f_kind, param=f_param, law=f_law, lp=f_lp,
            )

        def pick(stp, a, b):
            # per lane: the fault stream's value (a key, a mean, law
            # slots or None) where stp, else the false-prediction stream's
            return jax.tree.map(partial(jnp.where, stp), a, b)

        def adv_either(stp, act, ctr, tm):
            """One step of the stream each ``act`` lane seeks: the fault
            stream where ``stp``, else the false-prediction stream.  One
            counter draw per lane (a draw is a pure function of key and
            counter, so it has the bits of that stream's own step); the
            gap transform runs once where both streams share a law."""
            key, mean = pick(stp, fg_key, fp_key), pick(stp, mtbf, fp_mean)
            if f_kind == fp_kind and (f_kind == "indexed" or f_param == fp_param):
                return stream_advance(
                    act, ctr, tm, key, mean, horizon, kind=f_kind,
                    param=f_param, law=pick(stp, f_law, fp_law),
                    lp=pick(stp, f_lp, fp_lp),
                )
            f_ctr, f_tm = stream_advance(
                act, ctr, tm, key, mean, horizon,
                kind=f_kind, param=f_param, law=f_law, lp=f_lp,
            )
            p_ctr, p_tm = stream_advance(
                act, ctr, tm, key, mean, horizon,
                kind=fp_kind, param=fp_param, law=fp_law, lp=fp_lp,
            )
            return pick(stp, (f_ctr, f_tm), (p_ctr, p_tm))

        if has_window:
            # the pending-TP buffer: K slots per lane in window-start
            # order, ties in fault order (the host generator's stable
            # sort); one (K, L) array per field, the fault date and
            # counter only where the migration cancel reads them
            K = tp_slots
            slots = jnp.arange(K, dtype=jnp.int32)[:, None]
            buf_fill = (inf, nan, -1) if has_migration else (inf,)

            def buf_insert(buf, good, vals):
                """Insert each ``good`` lane's TP after every pending one
                whose window opens no later; returns its slot (K where
                nothing is inserted) and the buffer."""
                pos = jnp.where(
                    good,
                    jnp.sum(buf[0] <= vals[0], axis=0, dtype=jnp.int32), K,
                )
                return pos, tuple(
                    jnp.where(slots < pos, b, jnp.where(
                        slots == pos, v, jnp.concatenate([v[None], b[:-1]])
                    ))
                    for b, v in zip(buf, vals)
                )

            def buf_pop(buf, m):
                """Drop each ``m`` lane's head; the rest move up a slot."""
                return tuple(
                    jnp.where(
                        m, jnp.concatenate([b[1:], jnp.full_like(b[:1], f)]),
                        b,
                    )
                    for b, f in zip(buf, buf_fill)
                )

            def look(stp, la_time, head, npend, ovf):
                """Which ``stp`` lanes walk on: every fault not yet drawn
                comes at or after ``la_time`` and opens its window at or
                after ``la_time - window`` (the offset is below one window
                and rounding is monotone), and a tie sorts it after the
                head, so the head is final once that date reaches its
                window start.  A lane that must walk on with a full buffer
                cannot keep its predictions in order: it stops and is
                marked (``ovf``), never dropping one silently."""
                need = stp & (la_time - window < head)
                full = npend >= K
                return need & ~full, ovf | (need & full)

        def tp_draw(stp, sfp, la_ctr, la_time, fp_ctr, fp_time):
            """One counter draw of the stream each seeking lane seeks,
            and the TP side of it: the lookahead cursor moves, and
            ``good`` marks the lanes that drew a visible true positive."""
            ctr, tm = adv_either(
                stp, stp | sfp,
                *pick(stp, (la_ctr, la_time), (fp_ctr, fp_time)),
            )
            u_coin, u_off = counter_uniform2(tc_key, ctr, fdt)
            trusted = None
            if frac_q:
                trusted = counter_uniform(
                    pick(stp, tt_key, ft_key), ctr, fdt
                ) < q_eff
            alive = jnp.isfinite(tm)
            # a TP step: the lookahead cursor moves
            la_ctr = jnp.where(stp, ctr, la_ctr)
            la_time = jnp.where(stp, tm, la_time)
            vis = u_coin < recall
            if frac_q:
                vis &= trusted
            good = stp & vis & alive
            return ctr, tm, u_off, trusted, alive, la_ctr, la_time, good

        def fp_draw(sfp, ctr, tm, trusted, alive, fp_ctr, fp_time):
            """The FP side of a draw: the false-prediction cursor moves;
            with fractional trust the stream is thinned by per-event
            trust coins (without it every false prediction is seen)."""
            fp_ctr = jnp.where(sfp, ctr, fp_ctr)
            fp_time = jnp.where(sfp, tm, fp_time)
            sfp = sfp & ~trusted & alive if frac_q else jnp.zeros_like(sfp)
            return fp_ctr, fp_time, sfp

        def walk(stp, sfp, cur, mn, t):
            """Advance the prediction streams in one flat loop.

            A lane seeks at most one stream at a time: the next *visible*
            true positive (``stp``: the lookahead fault cursor walks until
            the recall coin, and with fractional trust the trust coin,
            admit a fault, or the stream dies at the horizon) or the next
            visible false prediction (``sfp``).  Each pass every seeking
            lane takes one step of its stream.  A lane of ``mn`` that
            seeks nothing then re-checks the skip rule
            (its merged head's action point has passed ``t``) and starts
            seeking the head's stream (ties to the TP).  So a lane
            consumes its streams in the same order, draw for draw, as a
            separate loop per stream would, and the loop runs as many
            passes as its busiest lane has draws.  Returns the cursors
            and the number of passes.

            With windows (``has_window``) the pending TPs sit in the
            window-ordered buffer, and ``stp`` marks the lanes that may
            walk the fault stream: a lane walks it while its head may not
            be final (:func:`look`), inserting every visible TP it
            draws; a skipped TP head leaves the buffer at once."""

            def skip(stp, sfp, tp_t0, fp_time):
                adv = mn & ~(stp | sfp) & (
                    jnp.minimum(tp_t0, fp_time) - lead_act < t
                )
                use_tp = tp_t0 <= fp_time
                return stp | (adv & use_tp), sfp | (adv & ~use_tp)

            def cond(c):
                return jnp.any(c[1] | c[2])

            def body(c):
                (n, stp, sfp, la_ctr, la_time, tp_t0, tp_ft, tp_ctr,
                 fp_ctr, fp_time) = c
                ctr, tm, u_off, trusted, alive, la_ctr, la_time, good = (
                    tp_draw(stp, sfp, la_ctr, la_time, fp_ctr, fp_time)
                )
                tp_t0 = jnp.where(
                    good, jnp.maximum(0.0, tm - u_off * window), tp_t0
                )
                tp_ft = jnp.where(good, tm, tp_ft)
                tp_ctr = jnp.where(good, ctr, tp_ctr)
                dead = stp & ~alive
                tp_t0 = jnp.where(dead, inf, tp_t0)
                tp_ft = jnp.where(dead, nan, tp_ft)
                fp_ctr, fp_time, sfp = fp_draw(
                    sfp, ctr, tm, trusted, alive, fp_ctr, fp_time
                )
                stp = stp & ~(good | dead)
                stp, sfp = skip(stp, sfp, tp_t0, fp_time)
                return (n + 1, stp, sfp, la_ctr, la_time, tp_t0, tp_ft,
                        tp_ctr, fp_ctr, fp_time)

            if not has_window:
                stp, sfp = skip(stp, sfp, cur[2], cur[6])
                n, _, _, *cur = lax.while_loop(
                    cond, body, (jnp.int32(0), stp, sfp, *cur)
                )
                return tuple(cur), n

            def skip_w(stp, sfp, la_time, buf, npend, fp_time):
                # as skip; a skipped TP head leaves the buffer now, and
                # the lane walks on where its new head may not be final,
                # else re-checks the rule on the next pass (``again``)
                head = buf[0][0]
                adv = mn & ~(stp | sfp) & (
                    jnp.minimum(head, fp_time) - lead_act < t
                )
                use_tp = head <= fp_time
                popt = adv & use_tp
                buf = buf_pop(buf, popt)
                npend = npend - popt.astype(npend.dtype)
                seek = popt & (la_time - window < buf[0][0])
                return (stp | seek, sfp | (adv & ~use_tp), popt & ~seek,
                        buf, npend)

            def cond_w(c):
                return jnp.any(c[1] | c[2] | c[3])

            def body_w(c):
                (n, stp, sfp, _, la_ctr, la_time, buf, npend, fp_ctr,
                 fp_time, ovt, peak, ovf) = c
                ctr, tm, u_off, trusted, alive, la_ctr, la_time, good = (
                    tp_draw(stp, sfp, la_ctr, la_time, fp_ctr, fp_time)
                )
                x_t0 = jnp.maximum(0.0, tm - u_off * window)
                pos, buf = buf_insert(
                    buf, good, (x_t0, tm, ctr) if has_migration else (x_t0,)
                )
                # an overtake: the TP's window opens before a pending one's
                ovt = ovt + (pos < npend).astype(ovt.dtype)
                npend = npend + good.astype(npend.dtype)
                peak = jnp.maximum(peak, npend)
                fp_ctr, fp_time, sfp = fp_draw(
                    sfp, ctr, tm, trusted, alive, fp_ctr, fp_time
                )
                stp, ovf = look(stp, la_time, buf[0][0], npend, ovf)
                stp, sfp, again, buf, npend = skip_w(
                    stp, sfp, la_time, buf, npend, fp_time
                )
                return (n + 1, stp, sfp, again, la_ctr, la_time, buf, npend,
                        fp_ctr, fp_time, ovt, peak, ovf)

            la_ctr, la_time, buf, npend, fp_ctr, fp_time, ovt, peak, ovf = cur
            stp, ovf = look(stp, la_time, buf[0][0], npend, ovf)
            stp, sfp, again, buf, npend = skip_w(
                stp, sfp, la_time, buf, npend, fp_time
            )
            n, _, _, _, *cur = lax.while_loop(
                cond_w, body_w,
                (jnp.int32(0), stp, sfp, again, la_ctr, la_time, buf, npend,
                 fp_ctr, fp_time, ovt, peak, ovf),
            )
            return tuple(cur), n

    def step(carry):
        it, nw, st = carry
        t = st["t"]
        saved, unsaved = st["saved"], st["unsaved"]
        period_work, na_saved = st["period_work"], st["na_saved"]
        ep_t0, ep_end = st["ep_t0"], st["ep_end"]
        phase = st["phase"]  # PH_DONE marks finished lanes (no done array)
        n_disk, n_det = st["n_disk"], st["n_det"]
        if has_two_level:
            saved_d, dk_ctr = st["saved_d"], st["dk_ctr"]
            rc = st["rc"]  # duration of the repair in progress
        else:
            rc = DR
        if has_silent:
            saved_v, ck_v = st["saved_v"], st["ck_v"]
            corrupt = st["corrupt"]
        if device_gen:
            fi = pi = None
            sf_ctr, sf_time = st["sf_ctr"], st["sf_time"]
            la_ctr, la_time = st["la_ctr"], st["la_time"]
            if has_window:
                # the buffer's head stands where the one slot stands
                buf = st["tp_buf"]
                tp_t0 = buf[0][0]
                tp_ft, tp_ctr = (buf[1][0], buf[2][0]) if has_migration else (
                    None, None)
                tp_n, tp_ovt = st["tp_n"], st["tp_ovt"]
                tp_peak, tp_ovf = st["tp_peak"], st["tp_ovf"]
            else:
                tp_t0, tp_ft, tp_ctr = st["tp_t0"], st["tp_ft"], st["tp_ctr"]
            fp_ctr, fp_time = st["fp_ctr"], st["fp_time"]
            if has_migration:
                ep_fctr = st["ep_fctr"]
                # retire cancel slots the strike cursor has passed
                cancels = tuple(
                    jnp.where(sf_ctr > st[k], -1, st[k])
                    for k in ("cancel0", "cancel1", "cancel2")
                )

                def is_cancelled(ctr):
                    return (
                        (ctr == cancels[0]) | (ctr == cancels[1])
                        | (ctr == cancels[2])
                    )
            else:
                ep_fctr = cancels = None
            Fcancel = None
        else:
            fi, pi = st["fi"], st["pi"]
            # lanes that can migrate carry the fault-cancellation mask;
            # all other sweeps compile a specialized step without it (it
            # would cost an (L, F) carry copy + three gathers every
            # iteration)
            Fcancel = st["Fcancel"] if has_migration else None
        ep_ft = st["ep_ft"] if has_migration else None

        prim = jnp.zeros_like(phase)  # int32, PRIM_NOOP
        target = jnp.zeros_like(t)
        cont = jnp.full_like(phase, -1)

        # ---- regular-mode decisions -------------------------------- #
        mn = phase == B._PH_MAIN

        if device_gen:
            # the merged (pending-TP, next-FP) head: the previous
            # iteration's walk (or the priming) already skipped every
            # prediction whose action point passed
            na = jnp.minimum(tp_t0, fp_time) - lead_act
        else:
            def p_cond(pi_):  # skip predictions whose action point passed
                return jnp.any(mn & (take(P0, pi_) - lead_act < t))

            def p_body(pi_):
                adv = mn & (take(P0, pi_) - lead_act < t)
                return pi_ + adv.astype(pi_.dtype)

            pi = lax.while_loop(p_cond, p_body, pi)
            na = take(P0, pi) - lead_act

        # clean-period fast-forward (same fusion rule as the NumPy engine)
        curf = sf_time if device_gen else take(F, fi)
        ffm = (
            mn & (period_work == 0.0) & (unsaved == 0.0) & (curf >= t)
        )
        if has_migration:
            if device_gen:
                ffm &= ~is_cancelled(sf_ctr)
            else:
                ffm &= ~take(Fcancel, fi)
        k_fault = jnp.floor((curf - t) / T_R)
        k_act = jnp.floor((na - t) / T_R)
        k_act = jnp.where(t + k_act * T_R >= na, k_act - 1.0, k_act)
        k_done = jnp.floor((W - saved - eps) / wpp)
        k_done = jnp.where(
            saved + k_done * wpp >= W - eps, k_done - 1.0, k_done
        )
        k = jnp.minimum(
            jnp.minimum(k_fault, k_act), jnp.minimum(k_done, 4e15)
        )
        # never fuse across a disk-tier or verification checkpoint (they
        # cost more than C): cap the run at the current stride remainder
        if has_two_level:
            k = jnp.where(
                tl_m,
                jnp.minimum(k, jnp.maximum(rho - 1.0 - dk_ctr, 0.0)), k,
            )
        if has_silent:
            k = jnp.where(
                sil_m,
                jnp.minimum(k, jnp.maximum(kv - 1.0 - ck_v, 0.0)), k,
            )
        ff = ffm & (k >= 2.0)
        t = jnp.where(ff, t + k * T_R, t)
        saved = jnp.where(ff, saved + k * wpp, saved)
        n_reg = st["n_reg"] + jnp.where(ff, k, 0.0).astype(st["n_reg"].dtype)
        if has_two_level:
            dk_ctr = jnp.where(ff & tl_m, dk_ctr + k, dk_ctr)
        if has_silent:
            ck_v = jnp.where(ff & sil_m, ck_v + k, ck_v)

        exhausted = st["exhausted"] | (mn & (t > horizon))
        remaining = wpp - period_work
        ck = mn & (remaining <= clock_tol(t, eps))
        prim = jnp.where(ck, B._PR_CKPT, prim)
        cont = jnp.where(ck, B._C_CKPTREG, cont)
        na_saved = jnp.where(ck, na, na_saved)
        wk_na = mn & ~ck & (na < t + remaining)
        wk_seg = mn & ~ck & ~wk_na
        prim = jnp.where(wk_na | wk_seg, B._PR_WORK, prim)  # credited work
        target = jnp.where(wk_na, na, jnp.where(wk_seg, t + remaining, target))
        cont = jnp.where(wk_na, B._C_POP_EP, jnp.where(wk_seg, B._C_MAIN, cont))

        # ---- episode entry ----------------------------------------- #
        # occupancy-gated (the NumPy engine's bincount gate): episode
        # phases are empty on the vast majority of iterations.  The big
        # Fcancel buffer stays OUT of the gating conds — an identity
        # branch would copy it every iteration.
        es = phase == B._PH_EP_START
        emig = es & (mode == B._M_MIGRATION)
        if has_migration:
            # the predicted fault hits the vacated node: cancel it
            can = emig & ~jnp.isnan(ep_ft) & (ep_ft >= t)
            if device_gen:
                # cancel by fault-counter index (stored at pop time) —
                # elementwise merges instead of an (L, F) match scan.
                # A cancel takes the first free slot, and each slot
                # retires once the strike cursor passes it, so the fill
                # order does not matter (windows pop TPs out of fault
                # order); a fourth simultaneously-pending cancel is
                # dropped.
                c0, c1, c2 = cancels
                f0 = c0 < 0
                f1 = ~f0 & (c1 < 0)
                f2 = ~f0 & ~f1 & (c2 < 0)
                if has_window:
                    # windows keep more cancels pending: one that finds
                    # no free slot is reported as an overflow
                    tp_ovf = tp_ovf | (can & ~(f0 | f1 | f2))
                cancels = (
                    jnp.where(can & f0, ep_fctr, c0),
                    jnp.where(can & f1, ep_fctr, c1),
                    jnp.where(can & f2, ep_fctr, c2),
                )
            else:
                # The O(L*F) match scan only runs on iterations where
                # some lane migrates; the (row, mask) delta crosses the
                # cond boundary (small arrays), never the Fcancel buffer
                # itself (an identity branch would copy it every
                # iteration), and the mark lands as one fused
                # elementwise OR.
                def _match(_):
                    m = (
                        (F == ep_ft[None, :])
                        & (frows >= fi[None, :])
                        & ~Fcancel
                    )
                    return (
                        jnp.argmax(m, axis=0).astype(jnp.int32),
                        can & m.any(axis=0),
                    )

                def _nomatch(_):
                    return jnp.zeros_like(fi), jnp.zeros_like(can)

                cj, setm = lax.cond(jnp.any(can), _match, _nomatch, 0)
                Fcancel = Fcancel | (setm[None, :] & (frows == cj[None, :]))

        def _ep_start(args):
            prim, target, cont = args
            prim = jnp.where(emig, B._PR_IDLE, prim)
            target = jnp.where(emig, ep_t0, target)
            cont = jnp.where(emig, B._C_MIG, cont)

            rest = es & ~(mode == B._M_MIGRATION)
            d = ep_t0 - C
            b1 = rest & (t < d)  # room for the pre-window checkpoint
            b2 = rest & ~(t < d) & (t <= d)  # exactly at t0 - C
            b3 = rest & (t > d)  # no time for the extra checkpoint
            prim = jnp.where(  # b1/b3: credited work (Alg. 1 line 12)
                b1 | b3, B._PR_WORK, jnp.where(b2, B._PR_CKPT, prim)
            )
            target = jnp.where(b1, d, jnp.where(b3, t, target))
            cont = jnp.where(
                b1, B._C_PRECKPT,
                jnp.where(b2, B._C_MODE, jnp.where(b3, B._C_NT2, cont)),
            )
            return prim, target, cont

        prim, target, cont = lax.cond(
            jnp.any(es), _ep_start, lambda a: a, (prim, target, cont)
        )

        # ---- pending episode primitives ---------------------------- #
        pmk = phase == B._PH_EP_PRECKPT
        prim = jnp.where(pmk, B._PR_CKPT, prim)
        cont = jnp.where(pmk, B._C_MODE, cont)

        nt2 = phase == B._PH_EP_NT2
        prim = jnp.where(nt2, PRIM_WORK_NC, prim)
        target = jnp.where(nt2, ep_t0, target)
        cont = jnp.where(nt2, B._C_MODE, cont)

        nck = phase == B._PH_EP_NOCKPT
        prim = jnp.where(nck, PRIM_WORK_NC, prim)
        target = jnp.where(nck, ep_end, target)
        cont = jnp.where(nck, B._C_MAIN, cont)

        wc = phase == B._PH_EP_WC

        def _wc(args):
            prim, target, cont, phase = args
            over = wc & (t >= ep_end - clock_tol(t, eps))
            phase = jnp.where(over, B._PH_MAIN, phase)  # window exhausted
            g = wc & ~over
            tp = jnp.where(jnp.isnan(T_P), tp_eff_default, T_P)
            seg = jnp.minimum(t + (tp - C), ep_end - C)
            wsel = g & (seg > t)
            gk = g & ~wsel
            prim = jnp.where(wsel, PRIM_WORK_NC, jnp.where(gk, B._PR_CKPT, prim))
            target = jnp.where(wsel, seg, target)
            cont = jnp.where(wsel, B._C_WC_CKPT, jnp.where(gk, B._C_WC, cont))
            return prim, target, cont, phase

        prim, target, cont, phase = lax.cond(
            jnp.any(wc), _wc, lambda a: a, (prim, target, cont, phase)
        )

        wck = phase == B._PH_EP_WC_CKPT
        prim = jnp.where(wck, B._PR_CKPT, prim)
        cont = jnp.where(wck, B._C_WC, cont)

        # ---- execute one primitive per lane ------------------------ #
        workm = (prim == B._PR_WORK) | (prim == PRIM_WORK_NC)
        ckm = prim == B._PR_CKPT
        res = prim != B._PR_NOOP
        # cap at job completion, pre-resolution clock (scalar order of ops)
        remw = W - saved - unsaved
        target = jnp.where(workm, jnp.minimum(target, t + remw), target)
        ckend = t + C  # only consulted under ckm
        # intent masks fixed with the end date (before stale-fault
        # resolution, mirroring the NumPy engine): the rho-th regular
        # ckpt of a two-level lane is the disk tier (cost C + C2), the
        # k_V-th regular ckpt of a silent-error lane verifies (cost
        # C + V).  Proactive ckpts hit the memory tier and never verify.
        if has_two_level or has_silent:
            reg_int = ckm & (cont == B._C_CKPTREG)
        if has_two_level:
            disk_int = reg_int & tl_m & (dk_ctr >= rho - 1.0)
            ckend = jnp.where(disk_int, ckend + C2, ckend)
        if has_silent:
            ver_int = reg_int & sil_m & (ck_v >= kv - 1.0)
            ckend = jnp.where(ver_int, ckend + V, ckend)

        # resolve stale faults (fault during downtime: recovery restarts;
        # rc is the duration of the repair in progress — D+R everywhere
        # except after a two-level disk recovery — and silent-error
        # strikes are not fail-stop events, so those lanes skip the
        # cascade entirely)
        res_f = res & ~sil_m if has_silent else res
        if device_gen:
            def s_cond(c):
                t_, ctr_, tm_, _ = c
                stale = tm_ < t_
                if has_migration:
                    stale |= is_cancelled(ctr_)
                return jnp.any(res_f & stale)

            def s_body(c):
                t_, ctr_, tm_, nflt_ = c
                if has_migration:
                    cc = is_cancelled(ctr_)
                    stepm = res_f & (cc | (tm_ < t_))
                    hit = stepm & ~cc & (tm_ >= t_ - rc)
                else:
                    stepm = res_f & (tm_ < t_)
                    hit = stepm & (tm_ >= t_ - rc)
                t_ = jnp.where(hit, tm_ + rc, t_)
                nflt_ = nflt_ + hit.astype(nflt_.dtype)
                ctr_, tm_ = adv_fault(stepm, ctr_, tm_)
                return t_, ctr_, tm_, nflt_

            t, sf_ctr, sf_time, n_faults = lax.while_loop(
                s_cond, s_body, (t, sf_ctr, sf_time, st["n_faults"])
            )
            nf = sf_time
        else:
            def s_cond(c):
                t_, fi_, _ = c
                cf = take(F, fi_)
                stale = cf < t_
                if has_migration:
                    stale |= take(Fcancel, fi_)
                return jnp.any(res_f & stale)

            def s_body(c):
                t_, fi_, nflt_ = c
                cf = take(F, fi_)
                if has_migration:
                    cc = take(Fcancel, fi_)
                    stepm = res_f & (cc | (cf < t_))
                    hit = stepm & ~cc & (cf >= t_ - rc)
                else:
                    stepm = res_f & (cf < t_)
                    hit = stepm & (cf >= t_ - rc)
                t_ = jnp.where(hit, cf + rc, t_)
                nflt_ = nflt_ + hit.astype(nflt_.dtype)
                fi_ = fi_ + stepm.astype(fi_.dtype)
                return t_, fi_, nflt_

            t, fi, n_faults = lax.while_loop(
                s_cond, s_body, (t, fi, st["n_faults"])
            )
            nf = take(F, fi)
        if has_silent:
            # silent strikes never interrupt a primitive (latent until
            # the next verification): mask them off the fail-stop check;
            # the refill inside the kernel is masked on `faulted`, so the
            # strike cursor of a silent lane stays untouched
            nf = jnp.where(sil_m, jnp.asarray(jnp.inf, nf.dtype), nf)
        if has_two_level:
            # tier coin consumed with the fault (read at the
            # pre-consumption cursor): u >= f sends recovery to disk
            if device_gen:
                u_tier = counter_uniform(tier_key, sf_ctr, horizon.dtype)
            else:
                u_tier = take(Ftier, fi)

        upd = masked_primitive_update if use_pallas else primitive_update
        kw = {"interpret": interpret} if use_pallas else {}
        if device_gen:
            # the struck fault is consumed: the sampling step (refill the
            # strike cursor with one counter draw where faulted) is fused
            # into the hot-step kernel itself.  The kernel contract wants
            # stream[2] == nf (the Pallas entry reads the cursor time off
            # the nf input), so the silent lanes' +inf mask rides along
            # and their true cursor — untouched by construction, silent
            # lanes never fault in the kernel — is restored afterwards
            if has_silent:
                sil_ctr, sil_time = sf_ctr, sf_time
            kw["stream"] = (fg_key, sf_ctr, nf, mtbf, horizon)
            if f_kind == "indexed":
                kw["stream"] += (f_law, f_lp[0], f_lp[1])
            kw["gap"] = (f_kind, f_param)
            with jax.named_scope("step_kernel"):
                t, saved, unsaved, period_work, flags, sf_ctr, sf_time = upd(
                    prim, cont, target, ckend, nf,
                    t, saved, unsaved, period_work, W, DR,
                    eps=eps, reg_cont=int(B._C_CKPTREG), **kw,
                )
            if has_silent:
                sf_ctr = jnp.where(sil_m, sil_ctr, sf_ctr)
                sf_time = jnp.where(sil_m, sil_time, sf_time)
        else:
            with jax.named_scope("step_kernel"):
                t, saved, unsaved, period_work, flags = upd(
                    prim, cont, target, ckend, nf,
                    t, saved, unsaved, period_work, W, DR,
                    eps=eps, reg_cont=int(B._C_CKPTREG), **kw,
                )
        faulted = (flags & FLAG_FAULTED) != 0
        ok = (flags & FLAG_OK) != 0
        fin = (flags & FLAG_FIN) != 0
        cok = (flags & FLAG_CKPT_OK) != 0
        reg = (flags & FLAG_REG) != 0

        if not device_gen:
            fi = fi + faulted.astype(fi.dtype)
        n_faults = n_faults + faulted.astype(n_faults.dtype)
        phase = jnp.where(faulted, B._PH_MAIN, phase)
        phase = jnp.where(fin, B._PH_DONE, phase)
        n_pro = st["n_pro"] + (cok & ~reg).astype(st["n_pro"].dtype)
        n_reg = n_reg + reg.astype(n_reg.dtype)

        if has_two_level:
            # disk-tier recovery: restart from the last disk ckpt (the
            # kernel already applied the memory-tier rollback t = nf+DR)
            disk = faulted & tl_m & (u_tier >= fmem)
            mem = faulted & tl_m & ~disk
            t = jnp.where(disk, nf + DR2, t)
            saved = jnp.where(disk, saved_d, saved)
            dk_ctr = jnp.where(disk, 0.0, dk_ctr)
            rc = jnp.where(mem, DR, jnp.where(disk, DR2, rc))
            n_disk = n_disk + disk.astype(n_disk.dtype)
            # completed disk-tier ckpt: promote the durable frontier;
            # completed memory-tier regular ckpt: advance the nesting
            # counter (proactive ckpts hit the memory tier but do not)
            dk = cok & disk_int
            saved_d = jnp.where(dk, saved, saved_d)
            dk_ctr = jnp.where(dk, 0.0, dk_ctr)
            dk_ctr = jnp.where(reg & tl_m & ~disk_int, dk_ctr + 1.0, dk_ctr)

        if has_silent:
            # consume latent strikes up to the new clock: they corrupt
            # state silently instead of interrupting the primitive
            silr = res & sil_m
            if device_gen:
                def sc_cond(c):
                    _, tm_, _ = c
                    return jnp.any(silr & (tm_ <= t))

                def sc_body(c):
                    ctr_, tm_, cor_ = c
                    hit = silr & (tm_ <= t)
                    cor_ = jnp.where(hit, jnp.minimum(cor_, tm_), cor_)
                    ctr_, tm_ = adv_fault(hit, ctr_, tm_)
                    return ctr_, tm_, cor_

                sf_ctr, sf_time, corrupt = lax.while_loop(
                    sc_cond, sc_body, (sf_ctr, sf_time, corrupt)
                )
            else:
                def sc_cond(c):
                    fi_, _ = c
                    return jnp.any(silr & (take(F, fi_) <= t))

                def sc_body(c):
                    fi_, cor_ = c
                    cf = take(F, fi_)
                    hit = silr & (cf <= t)
                    cor_ = jnp.where(hit, jnp.minimum(cor_, cf), cor_)
                    return fi_ + hit.astype(fi_.dtype), cor_

                fi, corrupt = lax.while_loop(
                    sc_cond, sc_body, (fi, corrupt)
                )
            # verification caught a latent corruption: roll back past
            # every unverified ckpt to the verified frontier
            vok = cok & ver_int
            det = vok & jnp.isfinite(corrupt)
            t = jnp.where(det, t + DR, t)
            saved = jnp.where(det, saved_v, saved)
            period_work = jnp.where(det, 0.0, period_work)
            corrupt = jnp.where(
                det, jnp.asarray(jnp.inf, corrupt.dtype), corrupt
            )
            n_faults = n_faults + det.astype(n_faults.dtype)
            n_det = n_det + det.astype(n_det.dtype)
            clean = vok & ~det
            saved_v = jnp.where(clean, saved, saved_v)
            ck_v = jnp.where(vok, 0.0, ck_v)
            ck_v = jnp.where(reg & sil_m & ~ver_int, ck_v + 1.0, ck_v)

        # ---- continuations on success ------------------------------ #
        cmask = ok & (phase != B._PH_DONE)
        cc = jnp.clip(cont, 0, CONT2PH.shape[0] - 1)
        phase = jnp.where(cmask, jnp.take(CONT2PH, cc), phase)

        n_mig = st["n_mig"] + (cmask & (cont == B._C_MIG)).astype(
            st["n_mig"].dtype
        )
        modem = cmask & (cont == B._C_MODE)
        phase = jnp.where(modem, jnp.take(MODE2PH, mode), phase)

        popm = cmask & (cont == B._C_POP_EP)
        ckr = cmask & (cont == B._C_CKPTREG)

        if device_gen:
            # pop the merged-head prediction into the episode registers;
            # for _C_CKPTREG (action point fell inside the regular
            # checkpoint) enter the episode only if the window start is
            # still current.  A few elementwise merges, so no cond.
            p0v = jnp.minimum(tp_t0, fp_time)
            takep = ckr & (na_saved <= t) & jnp.isfinite(p0v)
            good = takep & (p0v >= t - 1e-9)
            pop = popm | takep
            use_tp = pop & (tp_t0 <= fp_time)
            ep_t0 = jnp.where(pop, p0v, ep_t0)
            ep_end = jnp.where(pop, p0v + window, ep_end)
            phase = jnp.where(popm | good, B._PH_EP_START, phase)
            if has_migration:
                ep_ft = jnp.where(pop, jnp.where(use_tp, tp_ft, nan), ep_ft)
                ep_fctr = jnp.where(
                    pop, jnp.where(use_tp, tp_ctr, -1), ep_fctr
                )
            # one walk: refill the popped stream, then skip, for the next
            # iteration, every prediction whose action point has passed
            # (nothing reads the cursors before that iteration's na)
            if has_window:
                buf = buf_pop(buf, use_tp)
                tp_n = tp_n - use_tp.astype(tp_n.dtype)
                (la_ctr, la_time, buf, tp_n, fp_ctr, fp_time, tp_ovt,
                 tp_peak, tp_ovf), n = walk(
                    use_tp, pop & ~use_tp,
                    (la_ctr, la_time, buf, tp_n, fp_ctr, fp_time, tp_ovt,
                     tp_peak, tp_ovf),
                    phase == B._PH_MAIN, t,
                )
            else:
                (la_ctr, la_time, tp_t0, tp_ft, tp_ctr, fp_ctr, fp_time), n = (
                    walk(
                        use_tp, pop & ~use_tp,
                        (la_ctr, la_time, tp_t0, tp_ft, tp_ctr, fp_ctr,
                         fp_time),
                        phase == B._PH_MAIN, t,
                    )
                )
            nw = nw + n
        else:
            def _pop(args):
                # pop the prediction into the episode registers; for
                # _C_CKPTREG (action point fell inside the regular
                # checkpoint) enter the episode only if the window start
                # is still current.  ep_ft is only consulted by the
                # migration cancel, so the fast path neither carries nor
                # gathers it.
                if has_migration:
                    ep_t0, ep_ft, ep_end, pi, phase = args
                else:
                    ep_t0, ep_end, pi, phase = args
                p0v = take(P0, pi)
                takep = ckr & (na_saved <= t) & jnp.isfinite(p0v)
                good = takep & (p0v >= t - 1e-9)
                pop = popm | takep
                ep_t0 = jnp.where(pop, p0v, ep_t0)
                ep_end = jnp.where(pop, p0v + window, ep_end)
                pi = pi + pop.astype(pi.dtype)
                phase = jnp.where(popm | good, B._PH_EP_START, phase)
                if has_migration:
                    ep_ft = jnp.where(
                        pop, take(Pft, pi - pop.astype(pi.dtype)), ep_ft
                    )
                    return ep_t0, ep_ft, ep_end, pi, phase
                return ep_t0, ep_end, pi, phase

            if has_migration:
                ep_t0, ep_ft, ep_end, pi, phase = lax.cond(
                    jnp.any(popm | ckr), _pop, lambda a: a,
                    (ep_t0, ep_ft, ep_end, pi, phase),
                )
            else:
                ep_t0, ep_end, pi, phase = lax.cond(
                    jnp.any(popm | ckr), _pop, lambda a: a,
                    (ep_t0, ep_end, pi, phase),
                )

        st = {
            "t": t, "saved": saved, "unsaved": unsaved,
            "period_work": period_work, "na_saved": na_saved,
            "ep_t0": ep_t0, "ep_end": ep_end,
            "n_faults": n_faults, "n_pro": n_pro, "n_reg": n_reg,
            "n_mig": n_mig, "phase": phase,
            "exhausted": exhausted,
            "n_disk": n_disk, "n_det": n_det,
        }
        if has_two_level:
            st.update(saved_d=saved_d, dk_ctr=dk_ctr, rc=rc)
        if has_silent:
            st.update(saved_v=saved_v, ck_v=ck_v, corrupt=corrupt)
        if device_gen:
            st.update(
                sf_ctr=sf_ctr, sf_time=sf_time,
                la_ctr=la_ctr, la_time=la_time,
                fp_ctr=fp_ctr, fp_time=fp_time,
            )
            if has_window:
                st.update(tp_buf=buf, tp_n=tp_n, tp_ovt=tp_ovt,
                          tp_peak=tp_peak, tp_ovf=tp_ovf)
            else:
                st.update(tp_t0=tp_t0, tp_ft=tp_ft, tp_ctr=tp_ctr)
            if has_migration:
                st["ep_ft"] = ep_ft
                st["ep_fctr"] = ep_fctr
                st["cancel0"], st["cancel1"], st["cancel2"] = cancels
        else:
            st["fi"] = fi
            st["pi"] = pi
            if has_migration:
                st["ep_ft"] = ep_ft
                st["Fcancel"] = Fcancel
        return it + 1, nw, st

    def cond(carry):
        it, _, st = carry
        return jnp.any(st["phase"] != B._PH_DONE) & (it < max_iters)

    if device_gen:
        # prime the cursors: first strike fault, first visible TP (walks
        # the lookahead stream), first visible false prediction, then the
        # first iteration's skip.  Inert (padding) lanes never activate a
        # stream.
        state = dict(state)
        live = state["phase"] != B._PH_DONE
        neg1 = jnp.full_like(state["phase"], -1)
        zf = jnp.zeros_like(horizon)
        no_lane = jnp.zeros_like(live)
        sf_ctr, sf_time = adv_fault(live, neg1, zf)
        pvis = live & (q_eff > 0.0)
        fp_act = pvis & jnp.isfinite(fp_mean)
        if has_window:
            # an empty buffer, and zeroed overtake / peak counts
            zi = jnp.zeros_like(neg1)
            cur = (neg1, zf, tuple(
                jnp.full((tp_slots,) + zf.shape, f, d)
                for f, d in zip(buf_fill, (zf.dtype, zf.dtype, neg1.dtype))
            ), zi, neg1, jnp.where(fp_act, zf, inf), zi, zi, no_lane)
        else:
            cur = (neg1, zf, jnp.full_like(horizon, jnp.inf),
                   jnp.full_like(horizon, jnp.nan), neg1,
                   neg1, jnp.where(fp_act, zf, inf))
        cur, _ = walk(pvis & (recall > 0.0), no_lane, cur, no_lane,
                      state["t"])
        cur, _ = walk(no_lane, fp_act, cur, state["phase"] == B._PH_MAIN,
                      state["t"])
        if has_window:
            (la_ctr, la_time, buf, tp_n, fp_ctr, fp_time, tp_ovt, tp_peak,
             tp_ovf) = cur
            state.update(tp_buf=buf, tp_n=tp_n, tp_ovt=tp_ovt,
                         tp_peak=tp_peak, tp_ovf=tp_ovf)
        else:
            la_ctr, la_time, tp_t0, tp_ft, tp_ctr, fp_ctr, fp_time = cur
            state.update(tp_t0=tp_t0, tp_ft=tp_ft, tp_ctr=tp_ctr)
        state.update(
            sf_ctr=sf_ctr, sf_time=sf_time, la_ctr=la_ctr, la_time=la_time,
            fp_ctr=fp_ctr, fp_time=fp_time,
        )
        if has_migration:
            state["ep_ft"] = jnp.full_like(horizon, jnp.nan)
            state["ep_fctr"] = neg1
            state["cancel0"] = neg1
            state["cancel1"] = neg1
            state["cancel2"] = neg1

    # two-level / silent lane state materializes in-jit (the packers ship
    # none of it); the disk/detection counters ride along unconditionally
    # so the fetch path and the stats reduction see a fixed column set
    state = dict(state)
    zt = jnp.zeros_like(state["t"])
    zctr = jnp.zeros_like(state["n_faults"])
    state.setdefault("n_disk", zctr)
    state.setdefault("n_det", zctr)
    if has_two_level:
        state.setdefault("saved_d", zt)
        state.setdefault("dk_ctr", zt)
        state.setdefault("rc", jnp.broadcast_to(DR, zt.shape) + zt)
    if has_silent:
        state.setdefault("saved_v", zt)
        state.setdefault("ck_v", zt)
        state.setdefault("corrupt", jnp.full_like(state["t"], jnp.inf))

    n_it, n_walk, final = lax.while_loop(
        cond, step, (jnp.int32(0), jnp.int32(0), state)
    )
    # the outer loop's iteration count and the walk's passes ride out
    # beside the results; with windows also the buffer's counts: TPs that
    # overtook a pending one, the most any lane held, lanes it overflowed
    final = dict(final)
    counts = [n_it, n_walk]
    if has_window:
        counts += [
            jnp.sum(final["tp_ovt"], dtype=jnp.int32),
            jnp.max(final["tp_peak"]).astype(jnp.int32),
            jnp.sum(final["tp_ovf"], dtype=jnp.int32),
        ]
    final["_counts"] = jnp.stack(counts)
    if n_seg:
        # per-cell segment reduction on device: one (n_seg, 13) matrix of
        # Monte-Carlo sums per chunk instead of O(lanes) result fetches.
        # Padding lanes carry the sacrificial pad-row index, so their
        # degenerate waste (t = 0) lands in rows the host drops.
        ft = final["t"]
        fdt2 = ft.dtype
        waste = 1.0 - W / ft
        final["cell_sums"] = segment_cell_sums(
            [
                jnp.ones_like(ft),  # lane count
                ft, ft * ft,  # makespan moments
                waste, waste * waste,  # waste moments
                final["n_faults"].astype(fdt2),
                final["n_pro"].astype(fdt2),
                final["n_reg"].astype(fdt2),
                final["n_mig"].astype(fdt2),
                final["exhausted"].astype(fdt2),
                final["n_disk"].astype(fdt2),
                final["n_det"].astype(fdt2),
                (final["phase"] != B._PH_DONE).astype(fdt2),  # convergence
            ],
            cidx, n_seg,
        )
    return final


#: in-process runner registry, LRU-capped: a long-lived process (the
#: advisor-service path) sweeping many grid shapes would otherwise pin
#: every compiled executable forever.  64 keys comfortably covers any
#: one sweep's working set (pallas x migration x gen x device-set), and
#: evicted runners recompile cheaply through the persistent cache.
_RUN_CACHE: "OrderedDict" = OrderedDict()
_RUN_CACHE_MAX = 64

_cache_enabled = False


def enable_compilation_cache() -> None:
    """Persist compiled engine executables across processes.

    Repeated sweep invocations (separate processes hitting the same chunk
    shape / migration specialization) then skip XLA recompiles entirely:
    the in-process registry (``_RUN_CACHE``) already de-duplicates within
    a process, and JAX's persistent compilation cache extends it across
    processes.  The directory is ``JAX_COMPILATION_CACHE_DIR`` where that
    is set (JAX reads it itself; no directory is set here), and
    :data:`DEFAULT_CACHE_DIR` otherwise.  :func:`simulate_batch_jax`
    calls this on its first use; JAX opens the cache at its first
    compile that consults it.
    """
    import jax

    global _cache_enabled
    if _cache_enabled:
        return
    _cache_enabled = True
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # the engine's executables are small and quick to build one by one
    # but numerous (chunk shape x migration x precision), so cache
    # everything regardless of size / compile time
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def _resolve_devices(devices, mesh) -> list:
    """Normalize the ``devices=`` / ``mesh=`` knobs to a device list.

    ``devices`` accepts None (single default device — the bit-stable
    baseline), ``"all"``, an int (first n local devices), or an explicit
    sequence of jax devices; ``mesh`` accepts a ``jax.sharding.Mesh``
    whose device set is used (lane sharding is data-parallel, so only the
    flat device list matters)."""
    import jax

    if mesh is not None:
        if devices is not None:
            raise ValueError("pass either devices= or mesh=, not both")
        devs = list(np.asarray(mesh.devices).flat)
    elif devices is None:
        devs = [jax.devices()[0]]
    elif isinstance(devices, str):
        if devices != "all":
            raise ValueError(f"devices={devices!r} (expected 'all')")
        devs = list(jax.devices())
    elif isinstance(devices, int):
        avail = jax.devices()
        if not 1 <= devices <= len(avail):
            raise ValueError(
                f"devices={devices} but this process has {len(avail)} "
                "jax device(s); use XLA_FLAGS=--xla_force_host_platform_"
                "device_count=N to fake host devices"
            )
        devs = avail[:devices]
    else:
        devs = list(devices)
        if not devs:
            raise ValueError("devices= must name at least one device")
    return devs


class _ShardedRunner:
    """shard_map dispatch of the engine step over a 1-D ``("lanes",)``
    mesh.

    Lanes are mutually independent, so every per-lane array is
    partitioned on its lane axis while the O(cells) tables ride along
    replicated — each device runs the exact single-device program on its
    own shard (per-lane results are identical by construction, and each
    device's while-loop exits as soon as its own lanes finish).  In
    stats mode the per-cell segment sums are the *only* collective: one
    ``psum`` at chunk end folds them into the donated replicated
    accumulator, so nothing O(lanes) ever leaves the devices.

    The wrapped ``shard_map`` needs in/out specs matching the exact
    pytree structure, which varies with trace mode and migration state;
    they are built lazily from the first chunk's keys (one jit per key
    structure, cached)."""

    def __init__(self, step, devs, gathered, stats):
        from jax.sharding import Mesh

        self._step = step
        self._devs = devs
        self._gathered = gathered
        self._stats = stats
        self.mesh = Mesh(np.asarray(devs), ("lanes",))
        self._jitted = {}

    def _pspec(self, key):
        from jax.sharding import PartitionSpec as P

        if key in self._gathered:
            return P()  # replicated cell table
        if key in ("F", "P0", "Pft", "Fcancel", "Ftier"):
            return P(None, "lanes")  # (events, lanes) slab
        return P("lanes")

    def place(self, tree: dict) -> dict:
        """Explicitly shard one packed chunk pytree onto the mesh (lane
        arrays split, tables replicated) — no implicit transfers, so the
        dispatch stays legal under ``jax.transfer_guard("disallow")``."""
        import jax
        from jax.sharding import NamedSharding

        return {
            k: jax.device_put(v, NamedSharding(self.mesh, self._pspec(k)))
            for k, v in tree.items()
        }

    def __call__(self, consts, state, *acc):
        import jax
        from jax.sharding import PartitionSpec as P

        struct = (tuple(sorted(consts)), tuple(sorted(state)))
        fn = self._jitted.get(struct)
        if fn is None:
            cspec = {k: self._pspec(k) for k in consts}
            sspec = {k: self._pspec(k) for k in state}
            step = self._step
            # each device's counts (outer-loop count, walk passes, and
            # with windows the buffer's) leave as one row of ``_counts``:
            # (n_dev, n_counts) after the gather
            if self._stats:
                def body(c, s, a):
                    final = step(c, s)
                    cs = jax.lax.psum(final["cell_sums"], "lanes")
                    return _fold(a, cs), final["_counts"][None]

                fn = jax.jit(
                    jax.shard_map(
                        body, mesh=self.mesh,
                        in_specs=(cspec, sspec, P()),
                        out_specs=(P(), P("lanes")),
                        check_vma=False,
                    ),
                    donate_argnums=(1, 2),
                )
            else:
                def body(c, s):
                    final = step(c, s)
                    out = {k: final[k] for k in _OUT_KEYS}
                    out["_counts"] = final["_counts"][None]
                    return out

                fn = jax.jit(
                    jax.shard_map(
                        body, mesh=self.mesh,
                        in_specs=(cspec, sspec),
                        out_specs={k: P("lanes") for k in _OUT_KEYS + ("_counts",)},
                        check_vma=False,
                    ),
                    donate_argnums=(1,),
                )
            self._jitted[struct] = fn
        return fn(consts, state, *acc)


def _fold(acc, cs):
    """Add one chunk's per-cell sums into the running accumulator.

    The barrier keeps XLA from fusing the add into the segment
    reduction (which would sum each lane into the accumulator in turn):
    a chunk's sums are complete before they are folded in, so a call's
    result equals the host-side sum of per-chunk results at the same
    chunking bit for bit — the resumable campaign's contract."""
    import jax

    return acc + jax.lax.optimization_barrier(cs)


def _get_runner(
    use_pallas: bool, interpret: bool, max_iters: int, eps: float,
    has_migration: bool, devs, gen=None, gathered=(), n_seg=0,
    stats=False, has_two_level: bool = False, has_silent: bool = False,
    has_window: bool = False,
):
    import jax

    # a call with no window keeps one pending-TP slot: the exact-date
    # program
    tp_slots = WINDOW_TP_SLOTS if has_window else 1
    key = (
        use_pallas, interpret, max_iters, eps, has_migration,
        has_two_level, has_silent, tp_slots,
        tuple(d.id for d in devs), gen, gathered, n_seg, stats,
    )
    runner = _RUN_CACHE.get(key)
    if runner is not None:
        _RUN_CACHE.move_to_end(key)
        return runner
    step = partial(
        _jit_run, use_pallas=use_pallas, interpret=interpret,
        max_iters=max_iters, eps=eps, has_migration=has_migration,
        has_two_level=has_two_level, has_silent=has_silent,
        has_window=has_window, tp_slots=tp_slots,
        gen=gen, gathered=gathered, n_seg=n_seg,
    )
    if len(devs) > 1:
        runner = _ShardedRunner(step, devs, gathered, stats)
    elif stats:
        # fold this chunk's per-cell sums into the donated on-device
        # accumulator: the O(lanes) state never crosses the host boundary
        def run_stats(consts, state, acc):
            final = step(consts, state)
            return _fold(acc, final["cell_sums"]), final["_counts"]

        runner = jax.jit(run_stats, donate_argnums=(1, 2))
    else:
        runner = jax.jit(step, donate_argnums=(1,))
    _RUN_CACHE[key] = runner
    while len(_RUN_CACHE) > _RUN_CACHE_MAX:
        _RUN_CACHE.popitem(last=False)
    return runner


#: per-lane result arrays pulled back from the device after each chunk
_OUT_KEYS = (
    "t", "n_faults", "n_pro", "n_reg", "n_mig", "n_disk", "n_det",
    "exhausted", "phase",
)


def _chunk_state(sl: slice, n_pad: int, fdt, idt):
    """Zeroed per-lane engine state of one chunk (padding lanes inert).

    Every packed array is flat ``(n_pad,)`` regardless of device count —
    the sharded dispatch partitions the lane axis through ``shard_map``
    placement, not a host-side leading device axis."""
    n_real = sl.stop - sl.start
    phase = np.full(n_pad, B._PH_MAIN, np.int32)
    phase[n_real:] = B._PH_DONE  # padding lanes start inert
    zf = np.zeros(n_pad, fdt)
    zi = np.zeros(n_pad, idt)
    state = {
        "t": zf, "saved": zf, "unsaved": zf, "period_work": zf,
        "na_saved": zf, "ep_t0": zf, "ep_end": zf,
        "n_faults": zi, "n_pro": zi, "n_reg": zi, "n_mig": zi,
        "phase": phase,
        "exhausted": np.zeros(n_pad, bool),
    }
    return state


def _pack_scalar_chunk(
    sl: slice, n_pad: int, fdt, idt,
    W, C, D, R, M, T_R, T_P, mode, horizon, window, horizon_fill,
    cidx=None, pad_cell=0, tl=None, sil=None,
):
    """Shared scalar packing of one lane chunk (pure NumPy): the
    per-lane engine constants and zeroed lane state common to both trace
    modes.  Returns ``(fvec, consts, state)`` — the padding helper so
    callers can append their mode-specific arrays.

    ``cidx`` (fused sweeps, per-lane trace layouts) appends the lane ->
    cell index used by the device-side per-cell segment reduction;
    padding lanes map to the sacrificial ``pad_cell`` row."""
    state = _chunk_state(sl, n_pad, fdt, idt)

    def fvec(x, fill=0.0):
        return pad_lane_axis(x[sl], n_pad, fill).astype(fdt)

    Ch = fvec(C, 1.0)
    Mh = fvec(M, 1.0)
    modeh = pad_lane_axis(mode[sl], n_pad, 0).astype(np.int32)
    T_Rh = fvec(T_R, 2.0)
    windowh = fvec(window)
    consts = {
        "W": fvec(W, 1.0),
        "C": Ch,
        "DR": fvec(D) + fvec(R),
        "T_R": T_Rh,
        "T_P": fvec(T_P, np.nan),
        "mode": modeh,
        "horizon": fvec(horizon, horizon_fill),
        "window": windowh,
        "wpp": np.maximum(T_Rh - Ch, 1e-9),
        "lead_act": np.where(modeh == B._M_MIGRATION, Mh, Ch),
        "tp_eff_default": np.maximum(Ch, windowh),
    }
    if tl is not None:
        # two-level lanes: disk-tier cost/recovery, memory-tier
        # probability, nesting stride (benign pad fills, as in the tables)
        C2a, R2a, fmema, rhoa = tl
        consts["C2"] = fvec(C2a)
        consts["DR2"] = fvec(D) + fvec(R2a)
        consts["fmem"] = fvec(fmema)
        consts["rho"] = fvec(rhoa, 1.0)
    if sil is not None:
        Va, kva = sil
        consts["V"] = fvec(Va)
        consts["kv"] = fvec(kva, 1.0)
    if cidx is not None:
        consts["cidx"] = pad_lane_axis(
            cidx[sl].astype(np.int32), n_pad, pad_cell
        )
    return fvec, consts, state


def _stream_consts(spec: TraceSpec, sl: slice, n_pad: int) -> dict:
    """Per-lane RNG stream identity of one chunk: the two seed words and
    the two halves of the 64-bit stream id.  This layout is *the*
    invariant that makes device-generated results chunk-, device-count-
    and dispatch-invariant, so both spec packers share this one
    implementation."""

    def uvec(x):
        return pad_lane_axis(x, n_pad, 0).astype(np.uint32)

    stream = spec.stream[sl]
    return {
        "s0": uvec(np.full(stream.shape, spec.seed & 0xFFFFFFFF, np.int64)),
        "s1": uvec(
            np.full(stream.shape, (spec.seed >> 32) & 0xFFFFFFFF, np.int64)
        ),
        "sid_lo": uvec(stream & 0xFFFFFFFF),
        "sid_hi": uvec((stream >> 32) & 0xFFFFFFFF),
    }


#: consts keys shipped as per-cell tables (and device-gathered by the
#: lane -> cell index) in the fused TraceSpec dispatch
_CELL_TABLE_KEYS = (
    "W", "C", "DR", "T_R", "T_P", "mode", "horizon", "window",
    "wpp", "lead_act", "tp_eff_default", "mtbf", "fp_mean", "recall", "q_eff",
    "fault_law", "fault_s1", "fault_s2", "fp_law", "fp_s1", "fp_s2",
    "C2", "DR2", "V", "fmem", "rho", "kv",
)


def _cell_tables(
    n_cells: int, n_tab: int, fdt,
    W, C, D, R, M, T_R, T_P, mode, horizon, window, horizon_fill,
    mtbf=None, fp_mean=None, recall=None, q_eff=None,
    fault_laws=None, fp_laws=None,
    C2=None, R2=None, V=None, fmem=None, rho=None, kv=None,
) -> dict:
    """Per-cell engine-parameter tables of a fused sweep (pure NumPy).

    One row per experiment cell plus ``n_tab - n_cells`` benign padding
    rows carrying exactly the per-lane packing fills (row ``n_cells`` is
    the sacrificial row padding lanes index), so the device-side gather
    reproduces the unfused per-lane packing bit for bit.  ``n_tab`` is
    rounded up by the caller so grids of similar size share compiled
    executables."""

    def tab(x, fill=0.0, dt=None):
        a = np.full(n_tab, fill, dt or fdt)
        a[:n_cells] = np.asarray(x)
        return a

    Ch = tab(C, 1.0)
    Mh = tab(M, 1.0)
    modeh = tab(mode, 0, np.int32)
    T_Rh = tab(T_R, 2.0)
    windowh = tab(window)
    tables = {
        "W": tab(W, 1.0),
        "C": Ch,
        "DR": tab(np.asarray(D) + np.asarray(R)),
        "T_R": T_Rh,
        "T_P": tab(T_P, np.nan),
        "mode": modeh,
        "horizon": tab(horizon, horizon_fill),
        "window": windowh,
        "wpp": np.maximum(T_Rh - Ch, 1e-9).astype(fdt),
        "lead_act": np.where(modeh == B._M_MIGRATION, Mh, Ch).astype(fdt),
        "tp_eff_default": np.maximum(Ch, windowh).astype(fdt),
    }
    if mtbf is not None:
        tables.update(
            mtbf=tab(mtbf, 1.0),
            fp_mean=tab(fp_mean, np.inf),
            recall=tab(recall),
            q_eff=tab(q_eff),
        )
    if fault_laws is not None:
        # law multiplexing: int32 law index + the two slot parameters of
        # the branchless indexed gap transform, one row per cell (pad
        # rows are benign exponential / zero-slot rows)
        law, lp = fault_laws
        tables.update(
            fault_law=tab(law, 0, np.int32),
            fault_s1=tab(lp[:, 1]),
            fault_s2=tab(lp[:, 2]),
        )
    if fp_laws is not None:
        law, lp = fp_laws
        tables.update(
            fp_law=tab(law, 0, np.int32),
            fp_s1=tab(lp[:, 1]),
            fp_s2=tab(lp[:, 2]),
        )
    if C2 is not None:
        # two-level / silent-error columns (benign pad rows: degenerate
        # strides, zero extra costs, f = 0 sends every failure to disk)
        tables.update(
            C2=tab(C2),
            DR2=tab(np.asarray(D) + np.asarray(R2)),
            V=tab(V),
            fmem=tab(fmem),
            rho=tab(rho, 1.0),
            kv=tab(kv, 1.0),
        )
    return tables


def _pack_chunk_spec_cells(
    tables: dict, spec: TraceSpec, cidx, pad_cell: int,
    sl: slice, n_pad: int, fdt, idt,
):
    """Chunk packing of the fused (cell-indexed) TraceSpec dispatch.

    The engine parameters travel as O(cells) tables (replicated across
    devices by the shard_map placement); the only per-lane payload is
    the int32 cell index plus the RNG stream identity — the leanest
    possible packing, which is what lets one dispatch carry an entire
    paper grid."""
    state = _chunk_state(sl, n_pad, fdt, idt)
    consts = dict(tables)
    consts["cidx"] = pad_lane_axis(
        cidx[sl].astype(np.int32), n_pad, pad_cell
    )
    consts.update(_stream_consts(spec, sl, n_pad))
    return consts, state


def _pack_chunk(
    has_migration: bool, sl: slice, n_pad: int, fdt, idt,
    W, C, D, R, M, T_R, T_P, mode, F, P0, Pft, horizon, window,
    cidx=None, pad_cell=0, tl=None, sil=None, Ftier=None,
):
    """Host-side packing of one lane chunk into engine pytrees.

    Pure NumPy — no device work — so the async pipeline can pack chunk
    ``k+1`` while chunk ``k`` runs on the devices.  ``n_pad`` is the
    total padded lane count; the sharded dispatch splits the lane axis
    at placement time."""
    fvec, consts, state = _pack_scalar_chunk(
        sl, n_pad, fdt, idt,
        W, C, D, R, M, T_R, T_P, mode, horizon, window, np.inf,
        cidx=cidx, pad_cell=pad_cell, tl=tl, sil=sil,
    )

    def events(a):  # (n_pad, E) -> (E, n_pad)
        # (events, lanes) device layout — see the gather note in _jit_run
        return np.ascontiguousarray(a.T)

    consts.update(
        F=events(pad_lane_axis(F[sl], n_pad, np.inf).astype(fdt)),
        P0=events(pad_lane_axis(P0[sl], n_pad, np.inf).astype(fdt)),
        Pft=events(pad_lane_axis(Pft[sl], n_pad, np.nan).astype(fdt)),
    )
    if Ftier is not None:
        # per-fault recovery-tier coins, aligned column for column with F
        consts["Ftier"] = events(
            pad_lane_axis(Ftier[sl], n_pad, 1.0).astype(fdt)
        )
    state["fi"] = np.zeros(n_pad, np.int32)
    state["pi"] = np.zeros(n_pad, np.int32)
    if has_migration:
        state["ep_ft"] = np.full(n_pad, np.nan, fdt)
        state["Fcancel"] = np.zeros(consts["F"].shape, bool)
    return consts, state


def _pack_chunk_spec(
    spec: TraceSpec, fp_mean, q_eff, sl: slice, n_pad: int,
    fdt, idt, W, C, D, R, M, T_R, T_P, mode, cidx=None, pad_cell=0,
    f_laws=None, fp_laws=None, tl=None, sil=None,
):
    """Host-side packing of one lane chunk of a per-lane :class:`TraceSpec`.

    O(lanes) scalars only — no event arrays, no transpose, no
    O(events x lanes) host->device copy; the cursors are primed inside
    the jitted program from the per-lane stream ids, so the async
    pipeline's packing leg is essentially free in device trace mode.
    Padding lanes get horizon -1: every stream dies on its first draw
    (gaps are >= 1e-9), so inert lanes never sample.  ``f_laws`` /
    ``fp_laws`` (mixed-law per-lane specs) append the per-lane law index
    and slot parameters of the indexed gap transform."""
    fvec, consts, state = _pack_scalar_chunk(
        sl, n_pad, fdt, idt,
        W, C, D, R, M, T_R, T_P, mode, spec.horizon, spec.window, -1.0,
        cidx=cidx, pad_cell=pad_cell, tl=tl, sil=sil,
    )

    consts.update(
        mtbf=fvec(spec.mtbf, 1.0),
        fp_mean=fvec(fp_mean, np.inf),
        recall=fvec(spec.recall),
        q_eff=fvec(q_eff),
    )
    if f_laws is not None:
        law, lp = f_laws
        consts.update(
            fault_law=pad_lane_axis(
                law[sl].astype(np.int32), n_pad, 0
            ),
            fault_s1=fvec(lp[:, 1]),
            fault_s2=fvec(lp[:, 2]),
        )
    if fp_laws is not None:
        law, lp = fp_laws
        consts.update(
            fp_law=pad_lane_axis(law[sl].astype(np.int32), n_pad, 0),
            fp_s1=fvec(lp[:, 1]),
            fp_s2=fvec(lp[:, 2]),
        )
    consts.update(_stream_consts(spec, sl, n_pad))
    return consts, state


def _dispatch(runner, devs, consts, state, *acc):
    """Ship one packed chunk to the device(s) and start it (async).

    All transfers are explicit ``device_put``s (sharded placement through
    the runner's mesh when dispatch is multi-device), so engine dispatch
    is legal under ``jax.transfer_guard("disallow")``."""
    import jax

    if isinstance(runner, _ShardedRunner):
        consts = runner.place(consts)
        state = runner.place(state)
    else:
        consts = jax.device_put(consts, devs[0])
        state = jax.device_put(state, devs[0])
    with warnings.catch_warnings():
        # state buffers are donated (packed fresh per chunk), but CPU
        # lacks donation: scope the advisory's suppression to this call
        # so user code's own donation warnings stay visible
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        return runner(consts, state, *acc)


def _spec_draws(traces: TraceSpec, mode, q):
    """What a device-trace call draws on the device: ``(gen, q_eff,
    f_laws, fp_laws)``, the static draw specialization ``gen``, the
    engine-side trust and the law tables of mixed-law specs."""

    def _dist_static(d):
        # mixed-law specs carry one Distribution per cell (or lane):
        # the static (kind, param) specialization collapses to the
        # "indexed" sentinel and the laws travel as data tables
        if isinstance(d, tuple):
            for x in d:
                E.require_inverse_cdf(x)
            return "indexed", 0.0
        E.require_inverse_cdf(d)
        return d.kind, float(d.param)

    f_kind, f_param = _dist_static(traces.fault_dist)
    fp_kind, fp_param = _dist_static(traces.false_pred_dist)
    f_laws = (
        E.law_table(traces.fault_dist) if f_kind == "indexed" else None
    )
    fp_laws = (
        E.law_table(traces.false_pred_dist)
        if fp_kind == "indexed" else None
    )
    # engine-side trust: mode "none" / q<=0 sees no predictions,
    # fractional q thins both prediction streams via trust coins
    # (per-cell arrays in the fused layout — the gathered per-lane
    # values are identical, so is the compiled program); silent-error
    # lanes never trust the fail-stop predictor
    q_eff = np.where(
        (mode == B._M_NONE) | (mode == B._M_SILENT),
        0.0, np.clip(q, 0.0, 1.0),
    )
    frac_q = bool(((q_eff > 0.0) & (q_eff < 1.0)).any())
    return (f_kind, f_param, fp_kind, fp_param, frac_q), q_eff, f_laws, fp_laws


def _host_slabs(traces: BatchTraces, q, mode, fmem, any_tl: bool, rng):
    """A host-trace call's padded event slabs ``(F, P0, Pft, Ftier)``:
    fault times, the trusted predictions' dates and fault times, and
    (two-level lanes only, else None) each fault's tier."""
    p_t0, p_ft, _ = B._filter_trusted(traces, q, mode, rng)
    # pow2-rounded sentinel widths: chunks (and similarly-sized
    # batches) hit the same compiled executable
    F = pad_sentinel(traces.fault_times, traces.n_faults, np.inf,
                     round_pow2=True, min_width=8)
    P0 = pad_sentinel(p_t0, traces.n_preds, np.inf,
                      round_pow2=True, min_width=8)
    Pft = pad_sentinel(p_ft, traces.n_preds, np.nan,
                       round_pow2=True, min_width=8)
    if not any_tl:
        return F, P0, Pft, None
    FT = getattr(traces, "fault_tier", None)
    if FT is None:
        tl_lanes = mode == B._M_TWO_LEVEL
        if float(fmem[tl_lanes].max(initial=0.0)) > 0.0:
            raise ValueError(
                "two-level lanes with f > 0 need per-fault tier "
                "draws: generate traces with "
                "make_event_traces_batch(..., tier=True)"
            )
        FT = np.ones_like(traces.fault_times)
    elif FT.shape[1] < traces.fault_times.shape[1]:
        FT = np.concatenate(
            [FT, np.ones(
                (FT.shape[0],
                 traces.fault_times.shape[1] - FT.shape[1])
            )],
            axis=1,
        )
    Ftier = pad_sentinel(FT, traces.n_faults, 1.0,
                         round_pow2=True, min_width=8)
    return F, P0, Pft, Ftier


def _acc_init(n_seg: int, fdt, devs):
    """Zeroed on-device ``(n_seg, 13)`` CellSums accumulator.

    Donated through every chunk dispatch of a ``collect="stats"`` call
    (replicated across the lane mesh when sharded) and explicitly
    fetched exactly once at the end — the only O(cells) D2H of the
    whole call."""
    import jax

    z = np.zeros((n_seg, 13), fdt)
    if len(devs) == 1:
        return jax.device_put(z, devs[0])
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.asarray(devs), ("lanes",))
    return jax.device_put(z, NamedSharding(mesh, PartitionSpec()))


def _fetch(final, n_real: int):
    """Pull one dispatched chunk's per-lane results back to the host,
    with its loop counts per device under ``"_counts"``."""
    out = {k: np.asarray(final[k])[:n_real] for k in _OUT_KEYS}
    out["_counts"] = _count_rows(final["_counts"])
    n_open = int((out.pop("phase") != B._PH_DONE).sum())
    if n_open:
        raise RuntimeError(
            f"jax batch simulator did not converge: {n_open} lane(s) "
            "unfinished at max_iters"
        )
    return out


def _count_rows(c) -> np.ndarray:
    """One chunk's counts as ``(n_devices, _N_COUNTS)`` int64 rows; a
    call without windows reports none of the buffer's, which read 0."""
    c = np.asarray(c, np.int64)
    c = c.reshape(-1, c.shape[-1])
    return np.pad(c, ((0, 0), (0, _N_COUNTS - c.shape[1])))


def _collect(timings: dict, final, n_real: int):
    """Wait for one dispatched chunk, then :func:`_fetch` it; both add
    to ``timings["fetch_s"]``.  The copies are queued before the wait,
    so they start as the chunk ends: the wait adds no sync."""
    # the engine's one designed D2H point for per-lane results
    for k in _OUT_KEYS + ("_counts",):
        final[k].copy_to_host_async()  # repro-lint: disable=host-sync
    with span("repro.engine.wait", timings, "fetch_s"):
        final["t"].block_until_ready()  # repro-lint: disable=host-sync
    with span("repro.engine.fetch", timings, "fetch_s"):
        return _fetch(final, n_real)


#: column order of the device-side per-cell segment reduction
(
    _CS_N, _CS_T, _CS_T2, _CS_WASTE, _CS_WASTE2, _CS_NF, _CS_NPRO,
    _CS_NREG, _CS_NMIG, _CS_EXH, _CS_DISK, _CS_DET, _CS_NOTDONE,
) = range(13)


@dataclass
class CellSums:
    """Device-reduced per-cell Monte-Carlo sums of a fused sweep
    (``collect="stats"``): every field is an ``(n_cells,)`` array of
    sums over the cell's lanes, reduced on device and fetched as
    O(cells) scalars.  ``mean_*``/``ci95_*`` derive the usual summary
    statistics (CI via the ddof=1 sample variance)."""

    n: np.ndarray
    makespan_sum: np.ndarray
    makespan_sumsq: np.ndarray
    waste_sum: np.ndarray
    waste_sumsq: np.ndarray
    n_faults: np.ndarray
    n_proactive_ckpts: np.ndarray
    n_regular_ckpts: np.ndarray
    n_migrations: np.ndarray
    n_exhausted: np.ndarray
    n_disk_recoveries: np.ndarray
    n_detections: np.ndarray

    @property
    def n_cells(self) -> int:
        return int(self.n.shape[0])

    @staticmethod
    def _mean(s, n):
        with np.errstate(invalid="ignore", divide="ignore"):
            return s / n

    @staticmethod
    def _ci95(s, s2, n):
        with np.errstate(invalid="ignore", divide="ignore"):
            var = np.maximum(s2 - s * s / n, 0.0) / np.maximum(n - 1.0, 1.0)
            return np.where(n >= 2, 1.96 * np.sqrt(var / n), np.nan)

    @property
    def mean_waste(self) -> np.ndarray:
        return self._mean(self.waste_sum, self.n)

    @property
    def ci95_waste(self) -> np.ndarray:
        return self._ci95(self.waste_sum, self.waste_sumsq, self.n)

    @property
    def mean_makespan(self) -> np.ndarray:
        return self._mean(self.makespan_sum, self.n)

    @property
    def ci95_makespan(self) -> np.ndarray:
        return self._ci95(self.makespan_sum, self.makespan_sumsq, self.n)

    @classmethod
    def from_matrix(cls, cs: np.ndarray) -> "CellSums":
        return cls(
            n=cs[:, _CS_N], makespan_sum=cs[:, _CS_T],
            makespan_sumsq=cs[:, _CS_T2], waste_sum=cs[:, _CS_WASTE],
            waste_sumsq=cs[:, _CS_WASTE2], n_faults=cs[:, _CS_NF],
            n_proactive_ckpts=cs[:, _CS_NPRO],
            n_regular_ckpts=cs[:, _CS_NREG], n_migrations=cs[:, _CS_NMIG],
            n_exhausted=cs[:, _CS_EXH],
            n_disk_recoveries=cs[:, _CS_DISK],
            n_detections=cs[:, _CS_DET],
        )

    def as_matrix(self) -> np.ndarray:
        """The ``(n_cells, 12)`` column matrix (``_CS_*`` order, minus
        the internal not-done flag): sums are plain f64 adds, so partial
        sweeps accumulate by matrix addition — the resumable campaign's
        durable accumulator (:mod:`repro.ft.campaign`) is exactly this
        matrix summed chunk by chunk."""
        return np.stack(
            [
                np.asarray(self.n, np.float64),
                np.asarray(self.makespan_sum, np.float64),
                np.asarray(self.makespan_sumsq, np.float64),
                np.asarray(self.waste_sum, np.float64),
                np.asarray(self.waste_sumsq, np.float64),
                np.asarray(self.n_faults, np.float64),
                np.asarray(self.n_proactive_ckpts, np.float64),
                np.asarray(self.n_regular_ckpts, np.float64),
                np.asarray(self.n_migrations, np.float64),
                np.asarray(self.n_exhausted, np.float64),
                np.asarray(self.n_disk_recoveries, np.float64),
                np.asarray(self.n_detections, np.float64),
            ],
            axis=1,
        )


def simulate_batch_jax(
    work,
    platform: Union[Platform, Sequence[Platform]],
    strategy: Union[Strategy, Sequence[Strategy]],
    traces: Union[BatchTraces, TraceSpec],
    rng: Optional[np.random.Generator] = None,
    max_iters: int = 5_000_000,
    chunk: Union[int, str, None] = "auto",
    precision: str = "auto",
    use_pallas: bool = True,
    devices=None,
    mesh=None,
    cell_index=None,
    collect: str = "lanes",
) -> Union[BatchResult, "CellSums"]:
    """Device-resident :func:`repro.core.batch_sim.simulate_batch`.

    ``traces`` is either host-materialized :class:`BatchTraces` (the host
    trace mode) or a :class:`TraceSpec` (device trace mode): events are
    then sampled *inside* the engine from per-lane counter-based RNG
    streams — see the module docstring for the stream layout — and
    ``rng`` is ignored (fractional trust coins come from the lane's own
    trust streams, so results stay chunk- and device-count invariant).

    **Cell multiplexing** (fused experiment sweeps): ``cell_index`` maps
    every lane to one of ``n_cells`` experiment cells, and ``work`` /
    ``platform`` / ``strategy`` then describe *cells* (length
    ``n_cells``) instead of lanes.  With a cell-indexed
    :class:`TraceSpec` (required in device trace mode; defaulting
    ``cell_index`` from the spec) the engine parameters ship as O(cells)
    tables gathered on device.  The failure law itself is one of those
    tables: a cell-indexed spec may carry one ``Distribution`` *per
    cell* (tuple-valued ``fault_dist`` / ``false_pred_dist``), sampled
    through the branchless law-indexed gap transform — so ONE dispatch
    and one compiled executable per grid *shape* can run an entire
    mixed-law paper grid with lanes from many cells interleaved across
    chunks and shards.  Per-lane results are bit-identical to the
    equivalent per-lane call.  ``collect="stats"`` additionally
    segment-reduces per-cell Monte-Carlo sums on device into a donated
    accumulator and returns a :class:`CellSums` (one O(cells) fetch per
    call; per-lane arrays never reach the host) instead of per-lane
    arrays.

    Parameters beyond the NumPy engine's:

    chunk       total lanes resident across the device(s) at once
                ("auto": 5120-10240 on CPU — cache-sized chunks beat one
                giant batch there, and device trace mode fits twice the
                lanes per chunk — 16384 per device on accelerators;
                None: the whole batch).
                Chunks share one compiled executable (lane counts are
                padded to the Pallas tile and event widths rounded to
                powers of two).  Host-side packing of chunk ``k+1``
                overlaps device execution of chunk ``k`` (double-buffered
                async pipeline), and results are fetched one chunk
                behind the dispatch front.
    precision   "x64" (default off-TPU; float-rounding agreement with the
                NumPy engine), "x32" (the TPU default: f32 lanes,
                Threefry-x32 draws), or "auto".
    use_pallas  run the hot primitive-update step as the Pallas kernel
                (compiled on a TPU, interpret-mode elsewhere); False uses
                the identical pure-jnp body.
    devices     shard every chunk's lanes across these devices (None: the
                default device; "all": every local device; an int n: the
                first n local devices; or an explicit device sequence).
                Lanes are independent, so the sharded dispatch is a
                shard_map over a 1-D lane mesh (collective-free except
                for the single stats psum) and per-lane results are
                *identical* to the single-device path for any device
                count.
    mesh        a ``jax.sharding.Mesh``; shorthand for ``devices=`` over
                its (flattened) device set.  Mutually exclusive with
                ``devices=``.
    cell_index  (L,) int lane -> cell map; work/platform/strategy then
                have one entry per cell.  Defaults to the spec's own
                ``cell_index`` for cell-indexed :class:`TraceSpec`
                traces.
    collect     "lanes" (default): per-lane :class:`BatchResult`;
                "stats" (requires ``cell_index``): device-reduced
                per-cell :class:`CellSums`.
    """
    import jax

    enable_compilation_cache()
    is_spec = isinstance(traces, TraceSpec)
    spec_celled = is_spec and traces.cell_index is not None
    L = traces.n_lanes
    if collect not in ("lanes", "stats"):
        raise ValueError(
            f"unknown collect {collect!r} (expected 'lanes' or 'stats')"
        )
    if cell_index is None and spec_celled:
        cell_index = traces.cell_index
    celled = cell_index is not None
    if collect == "stats" and not celled:
        raise ValueError("collect='stats' requires cell_index")
    if celled and is_spec and not spec_celled:
        raise ValueError(
            "cell_index with a TraceSpec requires the cell-indexed "
            "layout (TraceSpec.cell_index)"
        )
    n_cells = 0
    if celled:
        cidx_g = np.asarray(cell_index, np.int32)
        if cidx_g.shape != (L,):
            raise ValueError(
                f"cell_index must have shape ({L},), got {cidx_g.shape}"
            )
        if spec_celled:
            n_cells = traces.n_cells
            if traces.cell_index is not cell_index and not np.array_equal(
                traces.cell_index, cidx_g
            ):
                raise ValueError(
                    "cell_index does not match traces.cell_index"
                )
        else:
            for arg in (platform, strategy):
                if not isinstance(arg, (Platform, Strategy)):
                    n_cells = len(arg)
                    break
            else:
                n_cells = int(cidx_g.max()) + 1 if L else 0
        if L and (cidx_g.min() < 0 or cidx_g.max() >= n_cells):
            raise ValueError(
                f"cell_index entries must be in [0, {n_cells})"
            )
    timings = {"pack_s": 0.0, "dispatch_s": 0.0, "fetch_s": 0.0}
    with span("repro.engine.prepare", lanes=L, cells=n_cells):
        W, C, D, R, M, T_R, T_P, mode, q, C2, R2, V, fmem, rho, kv = (
            B._lane_params(work, platform, strategy, n_cells if celled else L)
        )
        if celled and not is_spec:
            # host event arrays are inherently per-lane: broadcast the cell
            # table host-side (cheap NumPy gathers) and keep only the
            # lane -> cell index for the device-side per-cell reduction
            W, C, D, R, M, T_R, T_P, mode, q, C2, R2, V, fmem, rho, kv = (
                a[cidx_g] for a in (
                    W, C, D, R, M, T_R, T_P, mode, q, C2, R2, V, fmem, rho, kv
                )
            )
    # two-level / silent phase families are specialized out of every
    # other sweep's compiled step (and its packed payload), like migration
    any_tl = bool((mode == B._M_TWO_LEVEL).any())
    any_sil = bool((mode == B._M_SILENT).any())
    # device traces keep pending TPs in a window-ordered buffer where a
    # window lets a later fault's prediction open first; one program for
    # the whole grid, so a law-indexed grid stays one executable
    any_win = is_spec and bool((traces.window > 0.0).any())
    tl_extra = (C2, R2, fmem, rho) if any_tl else None
    sil_extra = (V, kv) if any_sil else None
    if L == 0:
        if collect == "stats":
            return CellSums.from_matrix(np.zeros((n_cells, 13)))
        z = np.zeros(0)
        zi = np.zeros(0, np.int64)
        return BatchResult(z, z, zi, zi, zi, zi, np.zeros(0, bool))
    with span("repro.engine.pack", timings, "pack_s"):
        if is_spec:
            gen, q_eff, f_laws, fp_laws = _spec_draws(traces, mode, q)
            fp_mean = traces.fp_mean
            F = P0 = Pft = None
        else:
            gen = None
            F, P0, Pft, Ftier = _host_slabs(traces, q, mode, fmem, any_tl, rng)

    devs = _resolve_devices(devices, mesh)
    n_dev = len(devs)
    backend = devs[0].platform
    if precision == "auto":
        precision = "x32" if backend == "tpu" else "x64"
    # Mosaic compiles the kernels on a TPU; every other backend interprets
    interpret = backend != "tpu"
    x64 = precision == "x64"

    if chunk == "auto":
        if backend == "cpu":
            # host devices share one cache hierarchy, so bound the TOTAL
            # resident lanes rather than scaling per device; x2 leaves the
            # async pipeline a second chunk in flight (measured optimum
            # across 1-8 forced host devices, see benchmarks/jax_engine)
            base = _DEFAULT_CHUNK_CPU_SPEC if is_spec else _DEFAULT_CHUNK_CPU
            chunk = base * min(n_dev, 2)
        else:
            chunk = _DEFAULT_CHUNK_DEV * n_dev
    chunk = L if chunk is None else min(int(chunk), L)
    # equal per-device shards, padded to the tile; single-device keeps the
    # LANE_TILE quantum so chunk shapes (hence compiled executables) are
    # unchanged from the unsharded engine
    quant = LANE_TILE if n_dev == 1 else SHARD_TILE
    per_dev_lanes = -(-chunk // n_dev)
    shard = -(-per_dev_lanes // quant) * quant
    n_pad = shard * n_dev

    # fused sweeps: pad the cell table with benign rows to a power of two
    # (row n_cells is the sacrificial row padding lanes point at), so
    # similarly-sized grids share compiled executables
    want_lanes = collect != "stats"
    if celled:
        n_tab = max(8, 1 << int(n_cells).bit_length())
        gathered = _CELL_TABLE_KEYS if spec_celled else ()
        # the per-cell segment reduction only runs when its output is
        # wanted; lanes-mode celled dispatches skip the reduction work
        n_seg = n_tab if collect == "stats" else 0
    else:
        n_tab = 0
        gathered, n_seg = (), 0

    with jax.enable_x64(x64):
        fdt = np.float64 if x64 else np.float32
        idt = np.int64 if x64 else np.int32
        with span("repro.engine.prepare", lanes=L, cells=n_cells):
            tables = None
            if spec_celled:
                tables = _cell_tables(
                    n_cells, n_tab, fdt,
                    W, C, D, R, M, T_R, T_P, mode,
                    traces.horizon, traces.window, -1.0,
                    mtbf=traces.mtbf, fp_mean=fp_mean,
                    recall=traces.recall, q_eff=q_eff,
                    fault_laws=f_laws, fp_laws=fp_laws,
                    C2=C2 if (any_tl or any_sil) else None,
                    R2=R2, V=V, fmem=fmem, rho=rho, kv=kv,
                )
            acc = None
            if not want_lanes:
                # per-cell sums accumulate *on device* across chunks (a
                # cell's lanes may straddle chunk boundaries): the donated
                # accumulator is carried through every dispatch and fetched
                # exactly once after the loop
                acc = _acc_init(n_seg, fdt, devs)
        outs = []
        counts = []  # each chunk's (outer-loop count, walk passes), per device
        pend = None  # the chunk in flight: (dispatched pytree, n_real)
        n_chunks = 0
        for lo in range(0, L, chunk):
            sl = slice(lo, min(lo + chunk, L))
            k = n_chunks
            n_chunks += 1
            # migration-free (and two-level-free, silent-free) chunks
            # compile a specialized step with none of that family's state
            chunk_mode = mode[cidx_g[sl]] if spec_celled else mode[sl]
            has_mig = bool((chunk_mode == B._M_MIGRATION).any())
            has_tl = bool((chunk_mode == B._M_TWO_LEVEL).any())
            has_sil = bool((chunk_mode == B._M_SILENT).any())
            runner = _get_runner(
                use_pallas, interpret, max_iters, float(_EPS), has_mig,
                devs, gen, gathered, n_seg, stats=not want_lanes,
                has_two_level=has_tl, has_silent=has_sil,
                has_window=any_win,
            )
            with span("repro.engine.pack", timings, "pack_s", chunk=k):
                if spec_celled:
                    consts, state = _pack_chunk_spec_cells(
                        tables, traces, cidx_g, n_cells,
                        sl, n_pad, fdt, idt,
                    )
                elif is_spec:
                    consts, state = _pack_chunk_spec(
                        traces, fp_mean, q_eff, sl, n_pad, fdt, idt,
                        W, C, D, R, M, T_R, T_P, mode,
                        f_laws=f_laws, fp_laws=fp_laws,
                        tl=tl_extra if has_tl else None,
                        sil=sil_extra if has_sil else None,
                    )
                else:
                    consts, state = _pack_chunk(
                        has_mig, sl, n_pad, fdt, idt,
                        W, C, D, R, M, T_R, T_P, mode, F, P0, Pft,
                        traces.horizon, traces.window,
                        cidx=cidx_g if celled else None, pad_cell=n_cells,
                        tl=tl_extra if has_tl else None,
                        sil=sil_extra if has_sil else None,
                        Ftier=Ftier if has_tl else None,
                    )
            arrays = (*consts.values(), *state.values())
            with span(
                "repro.engine.dispatch", timings, "dispatch_s", chunk=k,
                arrays=len(arrays), bytes=sum(a.nbytes for a in arrays),
            ):
                if want_lanes:
                    disp = _dispatch(runner, devs, consts, state)
                else:
                    acc, cnt = _dispatch(runner, devs, consts, state, acc)
                    # the counts' copy starts as their chunk ends
                    cnt.copy_to_host_async()  # repro-lint: disable=host-sync
                    counts.append(cnt)
            if want_lanes:
                if pend is not None:  # fetch one chunk behind the dispatch
                    outs.append(_collect(timings, *pend))
                pend = (disp, sl.stop - sl.start)
        if want_lanes:
            outs.append(_collect(timings, *pend))
            counts = [o.pop("_counts") for o in outs]
        else:
            # queued before the wait, the copy starts as the last chunk
            # ends: the wait adds no sync
            acc.copy_to_host_async()  # repro-lint: disable=host-sync
            with span("repro.engine.wait", timings, "fetch_s"):
                acc.block_until_ready()  # repro-lint: disable=host-sync
            with span("repro.engine.fetch", timings, "fetch_s"):
                # designed D2H point: one O(cells) stats matrix per run,
                # fetched with every chunk's loop counts
                cs, counts = jax.device_get((acc, counts))  # repro-lint: disable=host-sync
            cs = np.asarray(cs, np.float64)
    counts = np.stack([_count_rows(c) for c in counts])
    LAST_TIMINGS.clear()
    LAST_TIMINGS.update(
        trace_mode="device" if is_spec else "host", n_chunks=n_chunks,
        loop_iters=counts[..., 0], walk_passes=counts[..., 1],
        tp_overtakes=counts[..., 2], tp_pending_peak=counts[..., 3],
        tp_overflow=counts[..., 4],
        precision=precision, **timings,
        pallas=("interpret" if interpret else "compiled") if use_pallas else "off",
    )
    n_ovf = int(counts[..., 4].sum())
    if n_ovf:
        raise RuntimeError(
            f"jax batch simulator: {n_ovf} lane(s) had more than "
            f"{WINDOW_TP_SLOTS} true-positive predictions, or more than 3 "
            "migration cancellations, pending at once, so their "
            "predictions could not be served exactly"
        )
    if not want_lanes:
        n_open = int(cs[:n_cells, _CS_NOTDONE].sum())
        if n_open:
            raise RuntimeError(
                f"jax batch simulator did not converge: {n_open} lane(s) "
                f"unfinished after max_iters={max_iters} iterations"
            )
        return CellSums.from_matrix(cs[:n_cells])
    cat = {k: np.concatenate([o[k] for o in outs]) for k in outs[0]}
    return BatchResult(
        makespan=cat["t"].astype(np.float64),
        work=W[cidx_g] if spec_celled else W,
        n_faults=cat["n_faults"].astype(np.int64),
        n_proactive_ckpts=cat["n_pro"].astype(np.int64),
        n_regular_ckpts=cat["n_reg"].astype(np.int64),
        n_migrations=cat["n_mig"].astype(np.int64),
        trace_exhausted=cat["exhausted"],
        n_disk_recoveries=cat["n_disk"].astype(np.int64),
        n_detections=cat["n_det"].astype(np.int64),
    )


def device_interarrival_samples(
    dist, mean: float, n: int, seed: int = 0, stream: int = 0
) -> np.ndarray:
    """Draw ``n`` inter-arrival samples through the *device* sampling path
    (jnp threefry + inverse-CDF transform, counters ``0..n-1`` of the
    lane's fault-gap stream) — the exact per-draw function the engine's
    cursors evaluate.  Used by the statistical-fidelity tests (KS against
    the host :class:`~repro.core.events.Distribution` law) and fully
    deterministic in ``(seed, stream)``."""
    import jax
    import jax.numpy as jnp

    from ..kernels.sim_step import gap_transform, splitmix64

    E.require_inverse_cdf(dist)
    with jax.enable_x64(True):
        key = E.stream_key64_np(
            seed, np.asarray([stream], np.int64), E.STREAM_FAULT_GAP
        )
        ctr = jnp.arange(n, dtype=jnp.int64)  # event i <-> draw counter i
        x0, x1 = splitmix64(jnp.uint64(int(key[0])), ctr)
        g = gap_transform(
            dist.kind, float(dist.param), jnp.asarray(mean, jnp.float64),
            x0, x1, jnp.float64,
        )
        return np.asarray(g)
