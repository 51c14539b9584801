"""decode_step_ms.chat: device milliseconds of one decode program execution (model step layer), chat cells."""
from benchkit.readers import decode_step_ms as read  # noqa: F401
