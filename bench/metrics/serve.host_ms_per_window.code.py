"""serve.host_ms_per_window.code: `spans.host_ms_per_window` (moves
tok_per_s)."""
from spans import host_ms_per_window as read  # noqa: F401
