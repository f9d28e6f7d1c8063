"""Host seconds per request inside ``sweep()``: the span's wall time minus
the time in which some chip ran an operation (validation, stacking,
placement, packing and unpacking on the host)."""
from chipbench.measures import host_s_in


def read(ctx):
    return host_s_in(ctx, "sweep")
