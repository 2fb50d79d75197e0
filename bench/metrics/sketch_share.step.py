"""sketch_share.step: projection kernels' device time over all device busy
time in the step (%); the rest is bucketing, error feedback and AdamW."""
from bench.readers import projection_share as read  # noqa: F401
