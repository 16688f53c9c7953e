"""The output head and the loss against the compute roofline: 6 x hidden
x vocab FLOPs per token (forward and backward) for one chip's tokens of
a step, over the peak bf16 FLOP/s, divided by the device time under the
scope ``head_loss`` per step (final norm, head, softmax cross-entropy).
Bound: compute.
source: device_trace (lib/scopes.py)."""
from benchmark.lib import scope_roofline


def read(obs):
    return scope_roofline.percent(obs, "head_loss", ("head_loss",))
