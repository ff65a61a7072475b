from chipbench.metrics._common import mfu as read  # noqa: F401
