"""Host seconds per request in the program's ``repro.sweep.unpack`` spans
(reading the results back, accounting the engine's ticks, slicing each
lane's result, once per engine call) in which no chip ran an
operation."""
from chipbench.program_trace import host_s_in


def read(ctx):
    return host_s_in(ctx, "sweep.unpack")
