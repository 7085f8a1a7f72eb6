"""model.mfu: `reads.model_mfu` (moves tpot_p90_ms)."""
from reads import model_mfu as read  # noqa: F401
