"""Workload kinds, one file each, found by the ``kind`` of a configuration's
lane.  A kind module defines three functions:

* ``generate(params, shape, value) -> dict`` — the lane's data: what sets
  the fabric's work (sparsity pattern, graph, weights) drawn from the
  generator ``shape``, which is the same for every run, and the values
  that only the answer depends on from ``value``, the run's seed;
* ``build(data, cfg, strategy)`` — the system's compiler call (the only
  place a kind touches the program, imported inside the function);
* ``reference(data, dtype) -> np.ndarray`` — the plain numpy answer,
  computed with integer words of ``dtype``; it imports nothing of the
  program.
"""
