"""Where Pallas kernels run: compiled on a TPU, interpreted elsewhere."""
from __future__ import annotations

import jax


def interpret_mode(interpret=None) -> bool:
    """Run Pallas kernels compiled on a TPU and interpreted elsewhere;
    an explicit bool overrides (compile checks force ``False``)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
