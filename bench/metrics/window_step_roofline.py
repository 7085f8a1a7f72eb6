"""window_step_roofline: `reads.window_roofline` (moves tpot_p90_ms)."""
from reads import window_roofline as read  # noqa: F401
