"""Seconds per request spent in the system's compiler (the benchmark's
span around the ``build_*`` calls of one blocking request)."""
from chipbench.measures import per_request_span_s


def read(ctx):
    return per_request_span_s(ctx, "compile")
