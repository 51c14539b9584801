"""prefill_ms_per_ktok: host milliseconds in the blocking prefill per thousand prompt tokens (engine prefill layer)."""
from benchkit.readers import prefill_ms_per_ktok as read  # noqa: F401
