"""Share of the device's busy time under the scope ``dsa_select``: the
exact choice of ``index_topk`` keys a query from its causal scores (here a
radix select, 32 compare-and-count passes a block of queries, and the ties'
ranks), forward and recomputed forward; it has no backward. No matmul and
no roofline worth the name: latency- and VPU-bound work beside MXU-bound
neighbours, and what a later change to the selection would move.
source: device_trace (lib/sparse_flops.py's reduction)."""
from benchmark.lib import sparse_flops


def read(obs):
    busy = sparse_flops.seconds(obs, ("dsa_select",))
    r = sparse_flops.for_obs(obs) if busy else None
    if not r or not r["busy_s"]:
        return None
    return 100.0 * busy / r["busy_s"]
