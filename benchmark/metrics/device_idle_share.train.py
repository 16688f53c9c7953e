"""1 - (union of device-operation intervals) / traced window, mean over
the chips. source: device_trace."""
from benchmark.lib.trace import idle_share_percent as read  # noqa: F401
