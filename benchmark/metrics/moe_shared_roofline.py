"""The gated shared expert of every layer against the compute roofline:
forward and backward FLOPs of its SwiGLU (512 wide at hidden 2,048 in
Qwen3-Next) and of its gate's vector for one chip's tokens of a step over
the peak bf16 FLOP/s, divided by the device time per step under the scopes
``moe_shared`` and ``moe_shared_gate`` (``sigmoid(u . w)`` and its product,
inside it); the recomputed forward is in the time. Bound: compute.
source: device_trace (lib/delta_moe_flops.py's reduction)."""
from benchmark.lib import delta_moe_flops as lib


def read(obs):
    busy = lib.seconds(obs, ("moe_shared", "moe_shared_gate"))
    if not busy:
        return None
    m = obs["model"]
    return lib.percent_of_peak(
        obs, len(m["held"]["layer_kinds"]) * lib.shared_params(m), busy)
