"""Device busy nanoseconds, summed over the chips, per PE-step the service's
engine took in the traced window (the change of ``stepped_pe_ticks`` in
``SweepService.stats``, empty rows included)."""
from chipbench.measures import engine_ns_per_pe_tick


def read(ctx):
    return engine_ns_per_pe_tick(ctx)
