"""The service's mean share of stepped PE rows that carried a lane, over
the scheduler slices of the window (the change of ``occupancy_sum`` over
the change of ``n_slices`` in ``SweepService.stats``)."""
from chipbench.measures import stats_change


def read(ctx):
    slices = stats_change(ctx, "n_slices")
    if not slices:
        return None
    return stats_change(ctx, "occupancy_sum") / slices
