"""tune_cells_per_s: requested tunings returned per second, from the first
request issued to the last one completed (host clock)."""

from chipbench.readings import rate as read  # noqa: F401
