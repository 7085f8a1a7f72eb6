"""window_step_roofline.moe: `reads_moe.window_roofline`, the window's least
time by `counts_moe.py` over its device time (moves tpot_p90_ms)."""
from reads_moe import window_roofline as read  # noqa: F401
