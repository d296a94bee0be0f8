"""``step_mfu`` in the tensor-parallel cell: the model operations of every
token the window's requests had processed over the traced window times
the chips times the chip's peak.  The formula already counts the chips,
so the reader is ``step_mfu``'s own."""
from chipbench.metrics import step_mfu

read = step_mfu.read
