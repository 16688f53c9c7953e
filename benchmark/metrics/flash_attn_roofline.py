"""The flash kernels against the compute roofline: forward plus
backward FLOPs of causal attention for one chip's rows of a step, from
shapes, over the chip's peak bf16 FLOP/s, divided by the device time of
the step's Mosaic calls (which also hold the recomputed forward, whose
FLOPs are not counted). Bound: compute.
source: device_trace."""
from benchmark.lib import flops, peaks

PROGRAM = "jit_step"


def read(obs):
    tr, t = obs.get("trace"), obs.get("train")
    if not tr or not t or not t["traced_steps"]:
        return None
    m = tr.get("mosaic", {}).get(PROGRAM)
    if not m:
        return None
    tf = obs["traffic"]
    need = flops.flash_flops_per_step(obs["model"], tf["batch"] / t["chips"],
                                      tf["seq"])
    floor_s = need / peaks.peaks(obs["device"]["device_kind"])["bf16_flops"]
    return 100.0 * floor_s / (m["device_s"] / t["traced_steps"])
