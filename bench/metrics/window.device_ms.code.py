"""window.device_ms.code: `reads.window_device_ms` (moves tok_per_s)."""
from reads import window_device_ms as read  # noqa: F401
