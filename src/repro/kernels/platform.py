"""The platform check every Pallas kernel in this repo resolves against."""
from __future__ import annotations

import jax


def default_interpret() -> bool:
    """Interpret mode off only on real TPUs: a kernel called without
    ``interpret=`` compiles for the chip there and runs in the Pallas
    interpreter everywhere else."""
    return jax.default_backend() != "tpu"
