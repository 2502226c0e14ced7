"""Device operations per train step in the traced window (executor layer:
``run_pallas`` and its step plan)."""

from chipbench.readers import device_ops_per_step as read  # noqa: F401
