"""tpot_p95_ms: 95th percentile of every gap between consecutive output tokens that ends in the window."""
from benchkit.readers import tpot_p95_ms as read  # noqa: F401
