"""Kernel execution defaults shared by every Pallas entry point.

The kernels in this package compile to real TPU code; everywhere else
(CPU containers, CI) they can only run under the Pallas interpreter,
which executes the kernel body with jax ops grid-step by grid-step — a
silent ~100x slowdown if it ever lands on a serving hot path.  Entry
points therefore default ``interpret`` by platform (interpret only
off-TPU) instead of hard-coding ``True``.  Off-TPU,
``REPRO_PALLAS_INTERPRET`` overrides for validation runs:

    REPRO_PALLAS_INTERPRET=1   run the kernels in the interpreter
    REPRO_PALLAS_INTERPRET=0   compiled Pallas (fails without a TPU)

On a TPU a value asking for interpret mode raises instead of being
honoured: a chip run never executes the interpreter by way of a
variable left in the environment.

The serving backends (kernels/backend.py) go one step further and route
to jnp/XLA equivalents on non-TPU hosts, so interpret mode is reserved
for validation, never throughput.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

_ENV = "REPRO_PALLAS_INTERPRET"


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_requested() -> Optional[bool]:
    """The ``REPRO_PALLAS_INTERPRET`` override: None when unset, else
    whether it asks for interpret mode.  Raises on a TPU when it does."""
    env = os.environ.get(_ENV, "").strip()
    if env == "":
        return None
    wants = env not in ("0", "false", "False")
    if wants and on_tpu():
        raise RuntimeError(
            f"{_ENV}={env!r} asks for the Pallas interpreter on a TPU; "
            "unset it to run the compiled kernels"
        )
    return wants


def default_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve an entry point's ``interpret`` argument.

    Explicit ``True``/``False`` wins; ``None`` consults the env override,
    then the platform (compiled on TPU, interpreted elsewhere).
    """
    if interpret is not None:
        return interpret
    env = interpret_requested()
    if env is not None:
        return env
    return not on_tpu()
