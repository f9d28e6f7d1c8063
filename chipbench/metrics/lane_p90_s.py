"""The 90th percentile, by nearest rank, of the seconds from a lane's due
time to its result, over every lane the open-loop window sent.  A lane
that failed or never came lies beyond any limit; where the percentile
falls among them there is no number."""
from chipbench.measures import lane_percentile_s


def read(ctx):
    return lane_percentile_s(ctx, 0.90)
