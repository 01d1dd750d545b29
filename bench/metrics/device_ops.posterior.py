"""Device op events per iteration: the launches that ran, from the trace."""

from bench.readers import device_ops as read  # noqa: F401
