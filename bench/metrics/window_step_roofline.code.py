"""window_step_roofline.code: `reads.window_roofline` (moves tok_per_s)."""
from reads import window_roofline as read  # noqa: F401
