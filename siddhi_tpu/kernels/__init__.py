"""Hand-written Pallas kernels for the hot step (``@app:kernels``).

Three kernels replace the XLA-compiled hot loops, each pinned
bit-identical to the path it replaces and gated behind the planner the
same way the shard/multiplex/fuse/hotkey paths are:

- ``dense_step``  — plane-layout dense-NFA step: every (node,
  instance) pair is one int32 plane over the batch, a block of 1024
  batch rows one vreg per plane.
- ``bank_scatter`` — collision-free segmented reduce for the
  aggregation device bank, replacing the serializing scatter-add.
- ``scan_chain``  — one fused kernel for the hotkey scan's max-plus
  matrix chain + counting chain, replacing the two-pass
  ``associative_scan``.

A fourth kernel is behind no annotation:

- ``dense_run`` — the *run* of a skewed batch (one key hundreds of
  times): the dependence chain of ``DensePatternEngine.make_rounds`` in
  one kernel, its rows resident in VMEM from the first link to the
  last.  The engine takes it wherever its class is eligible and the
  kernel compiles, and keeps the XLA loop elsewhere.

Kernels compile through Mosaic on TPU and run under ``interpret=True``
everywhere else (``probe.interpret_mode()``).  Block shapes never depend
on the batch, so ``planner/kernels.py`` compiles each kernel once at app
creation and knows then whether it runs; every refused or ineligible
engine falls back to the XLA path with a counted
``kernelFallbackReason`` carrying the compiler's message.
"""
