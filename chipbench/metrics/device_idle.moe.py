from chipbench.metrics._common import device_idle as read  # noqa: F401
