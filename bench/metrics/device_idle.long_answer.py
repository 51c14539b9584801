"""device_idle.long_answer: share of the traced window with no operation on the device (device layer), long-answer cells."""
from benchkit.readers import device_idle as read  # noqa: F401
