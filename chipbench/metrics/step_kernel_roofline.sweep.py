"""The hot step kernel's share of its roofline, in percent.

The kernel (``kernels.sim_step.masked_primitive_update``: Pallas
``_step_kernel``, or ``_step_gen_kernel`` with the fault draw fused in) is
elementwise over the lanes, so HBM bandwidth bounds it: the least time of
one call is the bytes of its operands and results over the chip's peak
bandwidth, and the share is that over the mean device time of one call
in the trace."""

import re

#: the step kernel is the engine step's one Pallas call: a Mosaic custom
#: call in its HLO text, ``pallas_call`` in its JAX name, or the kernel's
#: own name where the trace keeps it
KERNEL = re.compile(r"tpu_custom_call|pallas_call|_step(_gen)?_kernel")
#: the compiled step names that call after the loop body it sits in
#: (``body.<n>``; the v5e compile of both configurations' steps has no
#: other instruction of that name)
KERNEL_OP = re.compile(r"^body\.\d+$")


def is_kernel(event) -> bool:
    name, what = event[0], event[3] if len(event) > 3 else ""
    return bool(KERNEL.search(name) or KERNEL.search(what)
                or KERNEL_OP.match(name))


def step_kernel_bytes(lanes: int, law_indexed: bool, x64: bool = False,
                      fused_draw: bool = True) -> int:
    """Bytes one call reads and writes, from the operand and result shapes.

    Every operand and result is one ``(lanes,)`` slab: int32 phase,
    continuation and flags; float clock, targets, work counters, job work
    and D + R; with the fused draw, the stream key (one uint64 in x64,
    two uint32 in x32), the int32 strike counter, the law's mean and
    horizon, and with law-indexed sampling the int32 law index and two
    shape slots.  Aliased operands are still read and written."""
    f = 8 if x64 else 4
    ins = 2 * 4 + 9 * f
    outs = 4 * f + 4
    if fused_draw:
        ins += (8 if x64 else 2 * 4) + 4 + 2 * f
        outs += 4 + f
        if law_indexed:
            ins += 4 + 2 * f
    return lanes * (ins + outs)


def call_shape(ctx) -> tuple:
    """``(lanes per call, law-indexed, x64)`` of the window's engine calls:
    one chunk of the program's lanes per chip."""
    from repro.core import jax_sim

    devs = ctx["devices"]
    return (jax_sim.default_chunk_lanes(devices=devs) // len(devs),
            len(ctx["cfg"]["fault_laws"]) > 1,
            jax_sim.LAST_TIMINGS.get("precision") == "x64")


def share(tr, nbytes: int, hbm_bytes_per_s: float):
    """Percent of the roofline of a call moving ``nbytes``, over the mean
    device time of the kernel's calls in trace ``tr``; None without any."""
    durs = [e[2] for evs in tr.ops.values() for e in evs if is_kernel(e)]
    if not durs:
        return None
    t_call = 1e-9 * sum(durs) / len(durs)
    return 100.0 * nbytes / hbm_bytes_per_s / t_call


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    return share(tr, step_kernel_bytes(*call_shape(ctx)),
                 ctx["peaks"]["hbm_bytes_per_s"])
