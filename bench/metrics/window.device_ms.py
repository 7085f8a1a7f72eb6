"""window.device_ms: `reads.window_device_ms` (moves tpot_p90_ms)."""
from reads import window_device_ms as read  # noqa: F401
