"""sketch_step_ms: the window's time over the steps it completed (ms)."""
from bench.readers import per_call_ms as read  # noqa: F401
