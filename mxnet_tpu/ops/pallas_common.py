"""Shared backend detection + constants for the Pallas kernels — one copy
so the kernel gates stay in lockstep."""
from __future__ import annotations

import jax

LANES = 128


def on_tpu() -> bool:
    """True when jax's default backend is a TPU. A backend that fails to
    initialise raises here: answering False would send every kernel to
    interpret mode or its XLA reference without a word."""
    return jax.devices()[0].platform == "tpu"


def resolve_interpret(interpret) -> bool:
    """The ``interpret=`` default of every kernel wrapper: compiled by
    Mosaic on a TPU backend, the Pallas interpreter elsewhere (the CPU
    tests). Interpret mode on a TPU backend is refused — there a kernel
    compiles or raises."""
    tpu = on_tpu()
    if interpret is None:
        return not tpu
    if interpret and tpu:
        raise RuntimeError(
            "Pallas interpret mode requested on a TPU backend; kernels "
            "compile with Mosaic there (interpret=True is for CPU tests)")
    return bool(interpret)
