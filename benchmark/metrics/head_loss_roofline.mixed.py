"""``head_loss_roofline`` for a configuration that holds a slice of its
vocabulary: 6 x hidden x the held rows of the head per token (forward and
backward) for one chip's tokens of a step, over the peak bf16 FLOP/s,
divided by the device time per step under the scope ``head_loss`` (final
norm, head, softmax cross-entropy). Bound: compute.
source: device_trace (lib/scopes.py)."""
from benchmark.lib import mixed_flops, scopes


def read(obs):
    if "held" not in obs.get("model", ()):
        return None
    return mixed_flops.percent_of_peak_in_scopes(
        obs, mixed_flops.head_params(obs["model"]),
        scopes.model_scope_seconds(obs, ("head_loss",)))
