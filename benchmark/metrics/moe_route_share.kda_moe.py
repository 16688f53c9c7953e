"""Share of the device's busy time under the scope ``moe_route`` of a
router under a group limit and a selection bias (``ops/moe.route_choice``):
the float32 router matmul over 512 experts, the sigmoid, the groups' sums
of their two largest ``s + b``, the top-k inside the kept groups, the sort
and the counts, and after the optimizer the bias's move
(``moe_bias_update``, which lies inside ``moe_route``): what a change to
the choice moves, which ``moe_dispatch_share`` folds in with the gathers.
source: device_trace (lib/kda_moe_flops.py's reduction)."""
from benchmark.lib import kda_moe_flops as lib


def read(obs):
    route = lib.seconds(obs, ("moe_route",))
    r = lib.for_obs(obs) if route is not None else None
    if not r or not r["busy_s"]:
        return None
    return 100.0 * route / r["busy_s"]
