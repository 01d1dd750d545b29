"""Host milliseconds per iteration inside the front-end call, up to its return."""

from bench.readers import dispatch_ms as read  # noqa: F401
