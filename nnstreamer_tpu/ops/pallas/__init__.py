"""Pallas TPU kernels and the rule that selects them."""

import jax


def per_platform(kernel, reference, interpret: bool, *args):
    """``kernel(*args)`` where the computation is placed on a TPU,
    ``reference(*args)`` on every other platform — decided per LOWERING
    platform (``jax.lax.platform_dependent``), i.e. by where the work
    runs, not by the process default device at trace time. Both branches
    are traced; only the chosen one is lowered. ``interpret=True``
    (tests) forces the kernel, which then carries its own interpreter
    flag."""
    if interpret:
        return kernel(*args)
    return jax.lax.platform_dependent(*args, tpu=kernel, default=reference)
