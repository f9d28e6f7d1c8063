"""Host seconds per request in the program's ``repro.pack.plan`` span
(cycle hints, the packer's wave plan and the certification of each packed
batch) in which no chip ran an operation."""
from chipbench.program_trace import host_s_in


def read(ctx):
    return host_s_in(ctx, "pack.plan")
