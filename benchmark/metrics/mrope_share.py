"""Share of the device's busy time under the scope ``mrope``: the rope
tables built once a forward from the batch's own positions in three streams
(``ops/layers.mrope_frequencies``: a gather of each frequency pair's stream,
a product, a cosine and a sine over ``[batch, seq, width / 2]``, for the
heads' width and the index's), forward and each recomputed forward that
makes them again. Memory- and VPU-bound work that a table from ``arange``
would not need: what positions from the batch cost.
source: device_trace (lib/sparse_gqa_flops.py's reduction)."""
from benchmark.lib import sparse_gqa_flops as sg


def read(obs):
    return sg.share_of_busy(obs, ("mrope",))
