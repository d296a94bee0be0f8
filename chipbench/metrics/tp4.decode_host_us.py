"""``decode_host_us`` in the tensor-parallel cell: the median decode
step's executor host time not spent waiting on the device, where the
step's arrays span four chips (the token's copy to them, the dispatch
of a program over four devices, the eager sampling of logits split over
them).  The reader is ``decode_host_us``'s own."""
from chipbench.metrics import decode_host_us

read = decode_host_us.read
