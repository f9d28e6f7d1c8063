"""The share of the tiles' SRAM that holds nonzeros: static AMs over tiles
x PEs x the per-PE word budget (``SweepReport.tiles.am_fill``), mean over
the window's requests; nothing where no request held a tiled lane."""


def read(ctx):
    fills = [r.tiles.am_fill for _, _, r in ctx.requests
             if getattr(r, "tiles", None) is not None]
    return sum(fills) / len(fills) if fills else None
