"""Simulated PE-cycles per wall second: every lane of every blocking
request in the window (a request still running when the window's seconds
ran out is finished and counted), over the seconds from the window's
start to the end of its last request."""
from chipbench.measures import pe_cycles


def read(ctx):
    if not ctx.requests:
        return None
    return pe_cycles(ctx.lanes) / ctx.window_s
