"""serve_ops_per_s: operations completed per second, from the first
request issued to the last one completed, counted by the client."""

from chipbench.readings import rate as read  # noqa: F401
