"""sched.occupancy: `spans.occupancy`, lanes holding a request over slots at
each profiled dispatch, in %."""
from spans import occupancy as read  # noqa: F401
