"""Share of the PE-ticks the service's engine stepped in the window that
simulated a cycle of a lane (the changes of ``live_pe_ticks`` and
``stepped_pe_ticks`` in ``SweepService.stats``); the rest stepped
finished lanes, the slice's tail or rows of no lane."""
from chipbench.measures import stats_change


def read(ctx):
    stepped = stats_change(ctx, "stepped_pe_ticks")
    if not stepped:
        return None
    return stats_change(ctx, "live_pe_ticks") / stepped
