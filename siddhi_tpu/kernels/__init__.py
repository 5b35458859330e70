"""Hand-written Pallas kernels, each chosen by the engine that runs it.

- ``dense_run`` — the *run* of a skewed batch (one key hundreds of
  times): the dependence chain of ``DensePatternEngine.make_rounds`` in
  one kernel, its rows resident in VMEM from the first link to the
  last.  The engine takes it wherever its class is eligible
  (``dense_run.eligible``) and the kernel compiles, and keeps the XLA
  loop elsewhere (``DensePatternEngine._make_run_kernel``); no
  annotation asks for it.
- ``row_scatter`` — the write-back of a batch's rows into the resident
  dense state (``DenseStateLayout.scatter``): one DMA a row, many in
  flight, the donated state aliased in and out, a padded lane no copy.
  Taken wherever the backend is a TPU and the state's rows are resident
  a vector of lanes at a time (``row_scatter.eligible``), which is where
  the layout made them so: rows wider than 128 words on one chip
  (``DenseStateLayout.row_shape``); XLA's scatter elsewhere.
- ``probe`` — whether a kernel compiles through Mosaic (on the TPU) or
  runs under ``interpret=True`` (everywhere else).
"""
