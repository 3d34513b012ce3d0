"""Idle share of the devices in the traced window (trace_reduce)."""

from benchmark.trace_reduce import idle_pct as read  # noqa: F401
