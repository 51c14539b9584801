"""decode_mfu.chat: decode model FLOPs in the traced window over its length and the bf16 peak (model step layer), chat cells."""
from benchkit.readers import decode_mfu as read  # noqa: F401
