"""Host seconds per request in the program's ``repro.sweep.place`` spans
(stacking, shard plan, cycle budget, placing the lane arrays on the chip
and building the initial state, once per engine call) in which no chip
ran an operation."""
from chipbench.program_trace import host_s_in


def read(ctx):
    return host_s_in(ctx, "sweep.place")
