"""moe.pairs_per_expert_step: `reads_moe.pairs_per_expert_step`, the rows
that share each read of a held expert's weights in the profiled windows
(moves tpot_p90_ms)."""
from reads_moe import pairs_per_expert_step as read  # noqa: F401
