"""Host seconds per request in the program's ``repro.sweep.validate`` span
(the static check of every lane before dispatch) in which no chip ran an
operation."""
from chipbench.program_trace import host_s_in


def read(ctx):
    return host_s_in(ctx, "sweep.validate")
