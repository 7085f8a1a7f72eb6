"""device.idle_share: `reads.idle_share` (moves tpot_p90_ms)."""
from reads import idle_share as read  # noqa: F401
