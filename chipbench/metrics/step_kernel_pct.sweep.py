"""Share of the step program's device busy time in the hot step kernel,
in percent: ops under the program's ``step_kernel`` scope (in a TPU
trace, the Pallas call the scope names ``%step_kernel.<n>``) over the
union of op intervals inside the step program's runs in the traced
window."""

from chipbench import program_trace as P


def read(ctx):
    tr = ctx.get("trace")
    return None if tr is None else P.scope_share(tr, "step_kernel")
