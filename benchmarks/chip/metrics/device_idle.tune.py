"""device_idle.tune: share of the traced window in which no operation ran on
the device, in percent."""

from chipbench.readings import idle_pct as read  # noqa: F401
