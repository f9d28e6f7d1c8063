"""Host seconds per request in the program's ``repro.compile.tile_plan``
span (the SpMV tile planner: per-column, per-PE counts and the column
blocks of an operand too large for the fabric) in which no chip ran an
operation."""
from chipbench.program_trace import host_s_in


def read(ctx):
    return host_s_in(ctx, "compile.tile_plan")
