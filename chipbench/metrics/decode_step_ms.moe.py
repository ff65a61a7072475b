from chipbench.metrics._common import decode_step_ms as read  # noqa: F401
