"""setup_s: process start to the start of the window: JAX start, weights, backend, warm-up of every program the traffic reaches, and the ramp."""
from benchkit.readers import setup_s as read  # noqa: F401
