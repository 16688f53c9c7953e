"""Operations and bytes of a decoder whose layers mix short convolutions
with attention (LFM2), by layer kind, from shapes; and the device time of
its step by the program's own names, the convolution's among them.

``model`` holds the Hugging Face names of the configuration file, with
``held`` (the layers this chip holds: ``layer_kinds`` such as
``conv+dense``, ``attn``, ``conv``, and ``num_experts_routed_over``),
``intermediate_size`` the dense MLP's width, ``moe_intermediate_size`` one
expert's and ``conv_L_cache`` the taps. A token multiplies its layer's
operator (a convolution's two projections, or attention's four), the
dense MLP or the router, and the head; the held experts multiply the rows
routed to them, which is data (the program's counter ``moe_rows_held``).
Attention is causal. Recomputed operations are never counted.

``lib/scopes.py`` and ``lib/moe_scopes.py`` know fixed tuples of names and
would send the convolution's operations to ``unscoped``; ``for_obs`` here
is their reduction with ``short_conv``, ``conv_in``, ``conv_mix``,
``conv_out`` and ``moe_bias_update`` added (same plane reader, same
self-time rule: the scopes add up to the device's busy time), cached
beside the trace as ``hybrid_scopes.json``. A program without these scopes
gives a reduction without them, and the readers return nothing.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, List, Optional, Tuple

from benchmark.lib import moe_scopes, peaks, scopes, trace

CONV_SCOPES = ("short_conv", "conv_in", "conv_mix", "conv_out")
HYBRID_SCOPES = (scopes.MODEL_SCOPES + moe_scopes.MOE_SCOPES + CONV_SCOPES
                 + ("moe_bias_update",))
_SCOPE_RE = re.compile(
    r"(?<![A-Za-z0-9_.])(" + "|".join(HYBRID_SCOPES)
    + r")(?![A-Za-z0-9_.])")


def layers(model: Dict) -> List[Dict[str, bool]]:
    """One entry a held layer: whether its operator is attention, whether
    its MLP is routed."""
    return [{"attn": kind.startswith("attn"), "routed": "dense" not in kind}
            for kind in model["held"]["layer_kinds"]]


def conv_proj_params(model: Dict) -> int:
    """One convolution operator's in- and out-projection (the taps are an
    elementwise pass, not a matmul)."""
    h = model["hidden_size"]
    return 3 * h * h + h * h


def attn_proj_params(model: Dict) -> int:
    """One attention operator's q and output projections, k and v."""
    h, hd = model["hidden_size"], model["head_dim"]
    return (2 * h * model["num_attention_heads"] * hd
            + 2 * h * model["num_key_value_heads"] * hd)


def count(model: Dict, **what: bool) -> int:
    """Held layers whose entry has all of ``what``."""
    return sum(all(layer[k] == v for k, v in what.items())
               for layer in layers(model))


def mlp_params(model: Dict) -> int:
    """The dense layers' SwiGLUs."""
    return (count(model, routed=False) * 3 * model["hidden_size"]
            * model["intermediate_size"])


def expert_params(model: Dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def head_params(model: Dict) -> int:
    return model["hidden_size"] * model["vocab_size"]


def token_matmul_params(model: Dict) -> int:
    """Parameters every token multiplies: each layer's operator, the dense
    MLP or the router, the head. Not the routed experts."""
    routers = (model["hidden_size"] * model["held"]["num_experts_routed_over"]
               * count(model, routed=True))
    return (count(model, attn=False) * conv_proj_params(model)
            + count(model, attn=True) * attn_proj_params(model)
            + mlp_params(model) + routers + head_params(model))


def attention_flops_fwd(model: Dict, batch: float, seq: int) -> float:
    """QK^T and PV of the attention layers, forward, over the (query, key)
    pairs the causal mask keeps."""
    return (count(model, attn=True) * batch * model["num_attention_heads"]
            * 4.0 * model["head_dim"] * seq * (seq + 1) / 2.0)


def flash_flops_per_step(model: Dict, batch: float, seq: int) -> float:
    """What the flash kernels of one train step must compute: forward (2
    matmuls) and backward (5), 3.5x the forward, as
    ``flops.flash_flops_per_step``."""
    return 3.5 * attention_flops_fwd(model, batch, seq)


def experts_train_flops(model: Dict, rows_held: float) -> float:
    return 6.0 * expert_params(model) * rows_held


def train_flops_per_step(model: Dict, batch: float, seq: int,
                         rows_held: float) -> float:
    """6 per matmul parameter and token, 6 per expert parameter and held
    row, attention forward and backward (3x the forward, as
    ``flops.train_flops_per_token``)."""
    return (6.0 * token_matmul_params(model) * batch * seq
            + experts_train_flops(model, rows_held)
            + 3.0 * attention_flops_fwd(model, batch, seq))


def conv_mix_bytes_per_step(model: Dict, tokens: float, remat: bool,
                            itemsize: int = 2) -> float:
    """The least bytes the pass between a convolution's two projections
    moves in one train step, all convolution layers: forward reads the
    in-projection's three thirds and writes one (4 channels-widths a
    token); backward reads those three thirds and the output's gradient
    and writes three (7); under rematerialisation the forward's are moved
    once more. The taps and their gradient are a few KB."""
    widths = 4 + 7 + (4 if remat else 0)
    return (count(model, attn=False) * widths * model["hidden_size"]
            * itemsize * tokens)


# ---- device time by scope, the convolution's scopes among the names

def scope_of(path: str) -> str:
    found = _SCOPE_RE.findall(path.split(";", 1)[0])
    return found[-1] if found else "unscoped"


def reduce_scopes(xplane_path: str) -> Dict[str, Any]:
    planes = scopes.read_planes(xplane_path)
    planes.pop("/host:CPU", None)
    chips = []
    for name in sorted(planes):
        ops = [ev for ln in planes[name]["lines"] if ln["name"] == "XLA Ops"
               for ev in ln["events"]]
        if ops:
            chips.append((planes[name], ops))
    by_scope: Dict[str, float] = {}
    busy_ns = 0.0
    for p, ops in chips:
        events = [(s, e, str(mid)) for mid, s, e in ops]
        for _s, _e, mid, self_ns, _leaf in trace._self_times(events):
            sc = scope_of(p["paths"].get(int(mid), ""))
            by_scope[sc] = by_scope.get(sc, 0.0) + self_ns / 1e9 / len(chips)
        busy_ns += trace.total(trace.union(
            [(s, e) for _, s, e in ops])) / len(chips)
    return {"chips": len(chips), "busy_s": busy_ns / 1e9,
            "scope_self_s": by_scope}


def for_obs(obs: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if not obs.get("trace") or "cell" not in obs:
        return None
    d = scopes.trace_dir_of(obs)
    cached = os.path.join(d, "hybrid_scopes.json")
    if os.path.exists(cached):
        with open(cached) as f:
            return json.load(f)
    try:
        reduced = reduce_scopes(trace.find_xplane(d))
    except FileNotFoundError:
        return None
    with open(cached, "w") as f:
        json.dump(reduced, f)
    return reduced


def seconds(obs: Dict[str, Any], names: Tuple[str, ...],
            need: Tuple[str, ...] = ()) -> Optional[float]:
    """Device seconds of the traced window (mean over chips) under those
    of ``names`` the trace has; nothing for a model without convolution
    layers, an untraced run, or a program that lacks one of ``need``."""
    t = obs.get("train")
    if (not t or not t["traced_steps"]
            or "conv_L_cache" not in obs.get("model", ())):
        return None
    r = for_obs(obs)
    if not r or any(n not in r["scope_self_s"] for n in need):
        return None
    return sum(r["scope_self_s"].get(n, 0.0) for n in names)


def percent_of_peak(obs: Dict[str, Any], params: int,
                    busy_s: Optional[float]) -> Optional[float]:
    """Forward and backward of ``params`` matmul parameters for one chip's
    tokens of a step (6 a parameter and token) at the chip's peak, as a
    share of ``busy_s`` device seconds of the traced window per step."""
    if not busy_s:
        return None
    t = obs["train"]
    return moe_scopes.percent_of_peak(
        obs, 6.0 * params * t["tokens_per_step"] / t["chips"], busy_s)


def percent_of_bandwidth(obs: Dict[str, Any], bytes_per_step: float,
                         busy_s: Optional[float]) -> Optional[float]:
    """``bytes_per_step`` of one chip at the chip's HBM bandwidth, as a
    share of ``busy_s`` device seconds of the traced window per step."""
    if not busy_s:
        return None
    floor_s = bytes_per_step / peaks.peaks(
        obs["device"]["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / (busy_s / obs["train"]["traced_steps"])
