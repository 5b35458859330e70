"""Where the Pallas kernels compile and where they are interpreted.

On a TPU backend the kernels go through Mosaic.  On anything else they
run under ``interpret=True`` — semantics-exact, speed-irrelevant — which
is what keeps the tier-1 differential tests meaningful on CPU.
"""

from __future__ import annotations


def interpret_mode() -> bool:
    """True when kernels must run interpreted (any non-TPU backend)."""
    import jax

    return jax.default_backend() != "tpu"
