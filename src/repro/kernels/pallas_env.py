"""Runtime rule for Pallas interpret mode (DESIGN.md §13).

Every Pallas kernel in ``kernels/`` needs the same decision at dispatch
time: run the compiled TPU kernel, or execute the identical kernel body
under ``interpret=True`` (pure-jax evaluation — numerics identical,
speed irrelevant) because no TPU is attached.  Interpret mode exists for
the CPU test suite only: on a TPU backend the kernels always compile, and
nothing (no flag, no environment variable) turns them back into the
interpreter.

Kernel wrappers keep an explicit ``interpret=`` parameter; ``None``
defers to :func:`use_interpret`.
"""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    """Is the active jax backend a TPU?"""
    return jax.default_backend() == "tpu"


def use_interpret() -> bool:
    """Should Pallas kernels run in interpret mode right now?  Exactly
    when the backend is not a TPU."""
    return not on_tpu()
