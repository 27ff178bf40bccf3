"""io_per_op: logical page I/Os per operation over the window, from the
engine's ``IOStats``."""

from chipbench.readings import io_per_op as read  # noqa: F401
