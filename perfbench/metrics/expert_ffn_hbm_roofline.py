"""Bytes the grouped expert products of one decode iteration need (the
touched held experts' weights once, the pairs' activations in and out) over
the HBM bandwidth, over the device time of those Mosaic calls, told apart
by result shape ([slots x experts per token, .])."""
from yardstick.kernel_readers import expert_ffn as read  # noqa: F401
