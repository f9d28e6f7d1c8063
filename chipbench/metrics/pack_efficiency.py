"""The packer's share of stepped PE rows that carry a lane
(``SweepReport.pack.packing_efficiency``), mean over the window's
requests."""


def read(ctx):
    effs = [r.pack.packing_efficiency for _, _, r in ctx.requests
            if r.pack is not None]
    return sum(effs) / len(effs) if effs else None
