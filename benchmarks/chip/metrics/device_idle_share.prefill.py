"""Share of the traced window in which no operation ran on the device."""

from chipbench.stats import idle_percent as read  # noqa: F401
