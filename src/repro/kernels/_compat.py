"""Device test and Pallas TPU compiler params shared by the kernel tier.

``interpret_default()`` is the dispatch rule of every ``ops.py``: a
kernel compiles with Mosaic on a TPU backend and runs in interpret mode
anywhere else.  ``on_tpu()`` is the one device test behind it.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.experimental.pallas import tpu as pltpu


def compiler_params(**kwargs: Any) -> pltpu.CompilerParams:
    """Pallas TPU compiler params (interpret mode ignores them)."""
    return pltpu.CompilerParams(**kwargs)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_default() -> bool:
    """Dispatch rule shared by the ops.py wrappers: interpret off-TPU."""
    return not on_tpu()
