"""The prediction module against the compute roofline: forward and backward
FLOPs of its joining matrix, its attention layer's projections and causal
scores (3 times the forward), its mixture's router, latent projections,
shared expert and held experts' rows (the module's own counts, mean over the
traced steps) and its pass of the head, for one chip's tokens of a step over
the peak bf16 FLOP/s, divided by the device time per step under the scope
``mtp``; the recomputed forward is in the time. Bound: compute.
source: device_trace (lib/scan_moe_flops.py's reduction) and
program_counter."""
from benchmark.lib import moe_scopes
from benchmark.lib import scan_moe_flops as lib


def read(obs):
    busy = lib.module_seconds(obs)
    t = obs.get("train") or {}
    if not busy or t.get("moe_rows_held_module_traced") is None:
        return None
    tf = obs["traffic"]
    return moe_scopes.percent_of_peak(
        obs, lib.module_flops_per_step(
            obs["model"], tf["batch"] / t["chips"], tf["seq"],
            t["moe_rows_held_module_traced"] / t["chips"]), busy)
