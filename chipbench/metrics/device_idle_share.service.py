"""Share of the traced window in which a chip ran no operation, averaged
over the chips the cell uses: between lanes, the service waits for
arrivals."""
from chipbench.measures import device_idle_share


def read(ctx):
    return device_idle_share(ctx)
