"""The MLP's matmuls against the compute roofline: 18 x hidden x ffn
FLOPs per token and layer (gate, up, down; forward and backward) for one
chip's tokens of a step, over the peak bf16 FLOP/s, divided by the device
time under the scope ``mlp`` per step (which also holds the norm, the
recomputed forward and, on several chips, the collectives of its
weights). Bound: compute.
source: device_trace (lib/scopes.py)."""
from benchmark.lib import scope_roofline


def read(obs):
    return scope_roofline.percent(obs, "mlp", ("mlp",))
