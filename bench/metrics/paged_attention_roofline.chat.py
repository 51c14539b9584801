"""paged_attention_roofline.chat: the paged_attention kernel's roofline time over its device time (kernel layer), chat cells."""
from benchkit.readers import paged_attention_roofline as read  # noqa: F401
