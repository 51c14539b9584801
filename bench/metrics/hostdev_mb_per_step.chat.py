"""hostdev_mb_per_step.chat: megabytes between host and device per decode step, from counts and shapes (KV backend layer), chat cells."""
from benchkit.readers import hostdev_mb_per_step as read  # noqa: F401
