"""The page-gather kernel against the memory roofline: the K/V bytes
that the pages of a decode step hold (every page that holds some of a
slot's context, whole, all layers; from the contexts the client saw while
the trace ran) over the chip's peak HBM bandwidth, divided by the
kernel's device time per step. Bound: memory bandwidth (a decode step
reads each byte once and does 2 FLOPs a byte per query head).
source: device_trace."""
from benchmark.lib import flops, peaks

PROGRAM = "jit_paged_decode_chunk"


def read(obs):
    tr, span = obs.get("trace"), obs.get("trace_span")
    if not tr or not span or PROGRAM not in tr.get("mosaic", {}):
        return None
    model, page = obs["model"], obs["traffic"]["engine"]["page_size"]
    m = tr["mosaic"][PROGRAM]
    steps = m["count"] / model["num_hidden_layers"]
    if not steps:
        return None
    # mean over sample times of the bytes one step reads
    t0, t1 = span
    n, total = 40, 0.0
    for i in range(n):
        t = t0 + (i + 0.5) * (t1 - t0) / n
        for r in obs["records"]:
            if not r["stamps"] or r["stamps"][0] > t:
                continue
            if (r["done"] or r["error"]) and r["stamps"][-1] < t:
                continue
            got = sum(c for s, c in zip(r["stamps"], r["counts"]) if s <= t)
            total += flops.paged_attention_bytes(
                model, len(r["req"]["prompt"]) + got, page, 1)
    bytes_per_step = total / n
    floor_s = bytes_per_step / peaks.peaks(
        obs["device"]["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / (m["device_s"] / steps)
