"""device_idle.chat: share of the traced window with no operation on the device (device layer), chat cells."""
from benchkit.readers import device_idle as read  # noqa: F401
