"""ingest_p95_ms: 95th percentile over every ingest due in the window, from
due time until the sketch is in the store (ms)."""
from bench.readers import p95_ms as read  # noqa: F401
