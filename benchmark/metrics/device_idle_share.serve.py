"""1 - (union of device-operation intervals) / traced window.
source: device_trace."""
from benchmark.lib.trace import idle_share_percent as read  # noqa: F401
