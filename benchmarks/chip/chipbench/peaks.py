"""The chips' published peaks (``peaks.json``), keyed by JAX's
``device_kind``.  A roofline or peak share divides by these; a chip that is
not in the table is an error, never a default."""

from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "peaks.json")


def peaks(device_kind: str) -> dict:
    """The peaks of one chip kind, or ``KeyError``."""
    with open(PATH) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PATH}")
    return table[device_kind]
