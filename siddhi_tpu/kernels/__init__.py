"""Hand-written Pallas kernels, each chosen by the engine that runs it.

- ``dense_run`` — the *run* of a skewed batch (one key hundreds of
  times): the dependence chain of ``DensePatternEngine.make_rounds`` in
  one kernel, its rows resident in VMEM from the first link to the
  last.  The engine takes it wherever its class is eligible
  (``dense_run.eligible``) and the kernel compiles, and keeps the XLA
  loop elsewhere (``DensePatternEngine._make_run_kernel``); no
  annotation asks for it.
- ``probe`` — whether a kernel compiles through Mosaic (on the TPU) or
  runs under ``interpret=True`` (everywhere else).
"""
