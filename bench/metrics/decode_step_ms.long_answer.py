"""decode_step_ms.long_answer: device milliseconds of one decode program execution (model step layer), long-answer cells."""
from benchkit.readers import decode_step_ms as read  # noqa: F401
