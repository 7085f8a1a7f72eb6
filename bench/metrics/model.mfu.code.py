"""model.mfu.code: `reads.model_mfu` (moves tok_per_s)."""
from reads import model_mfu as read  # noqa: F401
