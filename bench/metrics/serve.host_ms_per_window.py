"""serve.host_ms_per_window: `spans.host_ms_per_window`, the engine's own
host time per retired window (moves tpot_p90_ms)."""
from spans import host_ms_per_window as read  # noqa: F401
