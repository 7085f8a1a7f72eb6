"""device.idle_share.code: `reads.idle_share` (moves tok_per_s)."""
from reads import idle_share as read  # noqa: F401
