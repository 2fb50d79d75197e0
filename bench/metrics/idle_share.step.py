"""idle_share.step: 1 - device busy / window (%)."""
from bench.readers import idle_share as read  # noqa: F401
