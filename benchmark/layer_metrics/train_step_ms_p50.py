"""Median host time round one ``train_batch`` (ms)."""
from benchmark.readers import train_step_ms_p50 as read  # noqa: F401
