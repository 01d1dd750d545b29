"""The dense algorithm's least time over the device's busy time per iteration, in %."""

from bench.readers import roofline as read  # noqa: F401
