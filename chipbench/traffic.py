"""Traffic mixes.  A mix is a data file (``traffic/<mix>.json``) that
this module reads and the client its ``loop`` names drives
(:func:`chipbench.harness.make_client`):

* ``"loop": "closed"``, the default: :class:`chipbench.harness.ClosedSweep`,
  one client that sends the configuration's whole grid as one blocking
  ``sweep()`` and sends the next when it returns.  It reads ``pack``
  (the request's sub-mesh packing switch) and ``shard`` (split the lanes
  of each engine call over every chip of the host).
* ``"loop": "service"``: :class:`chipbench.service.OpenService`, single
  lanes of the grid due on an open-loop schedule at ``rate`` lanes per
  second, each compiled at its due time and submitted to one resident
  ``SweepService`` of ``n_supers`` super-lanes, engine chunks of
  ``chunk`` cycles and ``slice_chunks`` chunks per scheduler slice.

``trace_seconds``, read by both, caps the window of a ``--trace 1`` run
(closed, 1: one request): a TPU trace holds every op of every simulated
cycle (about 3.4 M events per Figs. 11-14 request), and reading it back
takes minutes.

Every request of a run carries the seed's grid, and every open-loop
schedule the same arrivals and lanes, the seed ordering the lanes, so
seeds change the data but not the sizes.
"""
from __future__ import annotations

import json

LOOPS = ("closed", "service")
SERVICE_KEYS = ("rate", "n_supers", "chunk", "slice_chunks")


def load(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if not float(mix.get("trace_seconds", 0)) > 0:
        raise ValueError(f"{path}: trace_seconds must be > 0")
    loop = mix.get("loop", "closed")
    if loop not in LOOPS:
        raise ValueError(f"{path}: loop {loop!r} is not one of {LOOPS}")
    if loop == "service":
        missing = [k for k in SERVICE_KEYS if k not in mix]
        if missing:
            raise ValueError(f"{path}: a service mix needs {missing}")
    return mix
