"""query_ms: the window's time over the queries it completed, one client in
a closed loop (ms)."""
from bench.readers import per_call_ms as read  # noqa: F401
