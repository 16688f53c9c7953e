"""Share of the device's busy time under the scope ``kda_gate``: the
bounded gate ``-5 sigmoid(exp(A_log) (f + dt_bias))`` over ``[tokens, H
K]`` float32 and ``beta``, forward and backward, of every KDA layer:
elementwise work over a float32 array the size of the keys, which a rule
with one decay a head does not have.
source: device_trace (lib/kda_moe_flops.py's reduction)."""
from benchmark.lib import kda_moe_flops as lib


def read(obs):
    gate = lib.seconds(obs, ("kda_gate",))
    r = lib.for_obs(obs) if gate is not None else None
    if not r or not r["busy_s"]:
        return None
    return 100.0 * gate / r["busy_s"]
