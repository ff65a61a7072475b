from chipbench.metrics._common import decode_roofline as read  # noqa: F401
