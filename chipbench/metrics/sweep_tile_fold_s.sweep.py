"""Host seconds per request in the program's ``repro.sweep.tile_fold``
span (folding each tiled lane's tiles back into one result) in which no
chip ran an operation."""
from chipbench.program_trace import host_s_in


def read(ctx):
    return host_s_in(ctx, "sweep.tile_fold")
