"""Share of the device's busy time under the scope ``moe_route`` of a
router that balances its load by a bias: the router matmul, the sigmoid,
the top-k on scores plus bias, the sort, the counts, and after the
optimizer the bias update (``moe_bias_update``, inside ``moe_route``).
Latency- and memory-bound work beside the matmuls.
source: device_trace (lib/hybrid_flops.py's reduction)."""
from benchmark.lib import hybrid_flops


def read(obs):
    busy = hybrid_flops.seconds(obs, ("moe_route", "moe_bias_update"),
                                need=("moe_route",))
    r = hybrid_flops.for_obs(obs) if busy else None
    if not r or not r["busy_s"]:
        return None
    return 100.0 * busy / r["busy_s"]
