"""sched.prefill_share: `spans.prefill_share`, prompt tokens fed over all
tokens the profiled windows processed, in %."""
from spans import prefill_share as read  # noqa: F401
