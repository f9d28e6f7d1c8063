"""Device busy nanoseconds, summed over the chips, per PE-step the engine
took in the traced window (``stepped_pe_ticks``, padded rows included)."""
from chipbench.measures import engine_ns_per_pe_tick


def read(ctx):
    return engine_ns_per_pe_tick(ctx)
