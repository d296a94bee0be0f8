"""``device_idle_share`` in the tensor-parallel cell: 1 - busy / window,
the busy time averaged over the four chips.  The reader is
``device_idle_share``'s own."""
from chipbench.metrics import device_idle_share

read = device_idle_share.read
