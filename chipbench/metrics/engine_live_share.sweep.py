"""Share of the PE-ticks the engine stepped in the window's requests that
simulated a cycle of a lane (``live_pe_ticks`` over ``stepped_pe_ticks``
of each request's ``EngineTelemetry``); the rest stepped finished lanes,
the chunk's tail or rows of no lane."""


def read(ctx):
    tels = [r.telemetry for _, _, r in ctx.requests]
    live = [getattr(t, "live_pe_ticks", None) for t in tels]
    stepped = sum(t.stepped_pe_ticks for t in tels)
    if not tels or None in live or stepped <= 0:
        return None
    return sum(live) / stepped
