"""Where Pallas interpret mode is chosen: from the platform, in one place."""
from __future__ import annotations

from typing import Optional

import jax


def pallas_interpret(interpret: Optional[bool] = None) -> bool:
    """Interpret mode for a ``pallas_call``.

    ``None`` (every kernel's default) interprets off a TPU and compiles on
    one. ``False`` compiles for the TPU even where the default backend is
    the CPU, for compiling against a described chip. Interpret mode on a
    TPU would run the kernel on the host and hide the chip, so it is
    refused.
    """
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode is not allowed on a TPU")
    return interpret
