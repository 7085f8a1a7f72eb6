"""model.mfu.moe: `reads_moe.model_mfu`, `counts_moe.py`'s operations of the
profiled windows over the profiled span times peak (moves tpot_p90_ms)."""
from reads_moe import model_mfu as read  # noqa: F401
