"""The pass between a convolution's two projections (gate, three taps,
gate) against the memory roofline: the least bytes it moves in a step
(``lib/hybrid_flops.conv_mix_bytes_per_step``: forward 4 widths a token,
backward 7, the forward's again under rematerialisation; one chip's
tokens, every convolution layer) over the HBM bandwidth, divided by the
device time per step under the scope ``conv_mix``. The pass is XLA's
fusions, not a kernel. Bound: memory bandwidth.
source: device_trace (lib/hybrid_flops.py's reduction)."""
from benchmark.lib import hybrid_flops


def read(obs):
    busy = hybrid_flops.seconds(obs, ("conv_mix",), need=("conv_mix",))
    if not busy:
        return None
    t = obs["train"]
    return hybrid_flops.percent_of_bandwidth(
        obs, hybrid_flops.conv_mix_bytes_per_step(
            obs["model"], t["tokens_per_step"] / t["chips"],
            remat=bool(obs["model"]["model_config"].get("remat", True))),
        busy)
