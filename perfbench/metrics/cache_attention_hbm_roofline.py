"""K and V bytes the decode attention calls of both cache kinds need (live
positions in full layers, the last window of each decoding slot in window
layers) over the HBM bandwidth, over the calls' device time; the kernel is
told apart by its result shape [slots, heads, v width]."""
from yardstick.kernel_readers import cache_attention as read  # noqa: F401
