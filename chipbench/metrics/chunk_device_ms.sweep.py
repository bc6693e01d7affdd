"""Device time of one engine-step program (one chunk of lanes through the
lane machine's while loop), in milliseconds: the mean duration of the
step program's events on the devices' ``XLA Modules`` lines, counting
only runs that start and end inside the traced window."""

import re

#: the engine's chunk programs: ``jit(run_stats)`` on one chip, the
#: ``shard_map`` body on several
STEP_PROGRAM = re.compile(r"^jit_(run_stats|body)\b")


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    durs = [
        d for evs in tr.modules.values() for name, s, d in evs
        if STEP_PROGRAM.match(name) and s >= tr.start_ns
        and s + d <= tr.end_ns
    ]
    return 1e-6 * sum(durs) / len(durs) if durs else None
