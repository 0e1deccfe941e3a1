"""Pallas TPU kernels for the paper's hot loops, one module each.

``ops`` holds the registry that pairs each kernel with its pure-jnp oracle
(``ref``).  Every kernel entry point takes ``interpret: bool | None = None``
and resolves it with :func:`interpret_mode` when it is traced.
"""
from __future__ import annotations

import os

import jax

# Off a TPU, the Pallas interpreter runs only where this is "1" (the CPU
# test suite sets it) or where a caller passes ``interpret=True``.
INTERPRET_ENV = "REPRO_PALLAS_INTERPRET"


def interpret_mode(interpret: bool | None) -> bool:
    """The ``interpret`` flag a kernel hands to ``pl.pallas_call``.

    On a TPU the kernels always compile, and ``interpret=True`` is refused.
    Elsewhere ``None`` means interpret only if :data:`INTERPRET_ENV` asks
    for it; otherwise the compiled kernel is requested, which JAX refuses
    to lower for a CPU rather than quietly running the interpreter.
    """
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu and os.environ.get(INTERPRET_ENV) == "1"
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode is never used on a TPU")
    return interpret
