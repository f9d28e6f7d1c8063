"""Traffic mixes.  A mix is a data file (``traffic/<mix>.json``) that
this module reads and :class:`chipbench.harness.ClosedSweep` drives: one
client sends the configuration's whole grid as one blocking ``sweep()``
and sends the next when it returns; ``pack`` is the request's switch.

``trace_seconds`` caps the window of a ``--trace 1`` run (1: one
request): a TPU trace holds every op of every simulated cycle (about
3.4 M events per Figs. 11-14 request), and reading it back takes minutes.

Every request of a run carries the seed's grid, so seeds change the data
but not the sizes.
"""
from __future__ import annotations

import json


def load(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if not float(mix.get("trace_seconds", 0)) > 0:
        raise ValueError(f"{path}: trace_seconds must be > 0")
    return mix
