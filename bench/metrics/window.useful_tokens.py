"""window.useful_tokens: `spans.useful_tokens`, prompt tokens fed plus tokens
committed per profiled window: the numerator of tok_per_s, a window at a
time."""
from spans import useful_tokens as read  # noqa: F401
