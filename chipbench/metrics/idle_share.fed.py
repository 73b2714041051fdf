"""Share of the traced window of the launcher cell in which no operation
ran on the device, in percent (as ``idle_share``)."""

from chipbench.metrics.idle_share import read  # noqa: F401
