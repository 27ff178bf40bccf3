"""reads_per_lookup: random page reads per point lookup over the window, from the
engine's ``IOStats``."""

from chipbench.readings import reads_per_lookup as read  # noqa: F401
