"""kernel_roofline.ingest: the projection kernels' roofline share (%)."""
from bench.readers import roofline as read  # noqa: F401
