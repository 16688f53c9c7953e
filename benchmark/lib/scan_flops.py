"""Operations and bytes of a decoder whose layers mix selective scans
(Mamba-2) with attention (Granite 4.0-H), by layer kind, from shapes; and
the device time of its step by the program's own names, the scan's among
them.

``model`` holds the Hugging Face names of the configuration file
(``mamba_n_heads``, ``mamba_d_head``, ``mamba_d_state``,
``mamba_n_groups``, ``mamba_d_conv``, ``mamba_chunk_size``,
``shared_intermediate_size``), with ``held["layer_kinds"]`` the layers this
chip holds (``mamba`` or ``attention``). A token multiplies its layer's
mixer (a scan layer's in- and out-projection, or attention's four), the
layer's SwiGLU and the head. Attention is causal. Recomputed operations
and the bytes they move are never counted.

The selective scan's operations are the chunked (SSD) algorithm's at the
published chunk ``Q``, over the pairs the causal mask keeps inside a chunk
(``Q (Q + 1) / 2``): a chunk's ``C B^T`` (``2 N`` a pair and group), its
product with ``x`` (``2 P`` a pair and head), the state a chunk hands on
and what the state it was handed gives (``2 Q P N`` a head each). Its
least bytes are what it must read and write once: ``x``, ``B``, ``C``
(the activations' dtype) and ``dt`` (float32) in, ``y`` out; the backward
reads those and ``dy`` and writes the four gradients.

``for_obs`` is ``lib/hybrid_flops.py``'s reduction with the scan's scopes
(``ssm``, ``ssm_in``, ``ssm_conv``, ``ssm_scan``, ``ssm_norm``,
``ssm_out``) in place of the convolution's (same plane reader, same
self-time rule: the scopes add up to the device's busy time), cached
beside the trace as ``scan_scopes.json``. A program without these scopes
gives a reduction without them, and the readers return nothing.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, Optional, Tuple

from benchmark.lib import moe_scopes, peaks, scopes, trace

SSM_SCOPES = ("ssm", "ssm_in", "ssm_conv", "ssm_scan", "ssm_norm", "ssm_out")
SCAN_MODEL_SCOPES = scopes.MODEL_SCOPES + SSM_SCOPES
_SCOPE_RE = re.compile(
    r"(?<![A-Za-z0-9_.])(" + "|".join(SCAN_MODEL_SCOPES)
    + r")(?![A-Za-z0-9_.])")


def is_scan_model(obs: Dict[str, Any]) -> bool:
    return "mamba_d_state" in obs.get("model", ())


def count(model: Dict, kind: str) -> int:
    """Held layers of ``kind`` (``mamba`` or ``attention``)."""
    return model["held"]["layer_kinds"].count(kind)


def ssm_inner(model: Dict) -> int:
    return model["mamba_n_heads"] * model["mamba_d_head"]


def ssm_conv_dim(model: Dict) -> int:
    """The channels the taps run over: x, B and C."""
    return (ssm_inner(model)
            + 2 * model["mamba_n_groups"] * model["mamba_d_state"])


def ssm_proj_params(model: Dict) -> int:
    """One scan layer's in-projection (to z, x B C and dt) and
    out-projection."""
    h, d = model["hidden_size"], ssm_inner(model)
    return (h * (d + ssm_conv_dim(model) + model["mamba_n_heads"])
            + d * h)


def attn_proj_params(model: Dict) -> int:
    """One attention layer's q and output projections, k and v."""
    h, hd = model["hidden_size"], model["head_dim"]
    return (2 * h * model["num_attention_heads"] * hd
            + 2 * h * model["num_key_value_heads"] * hd)


def mlp_params(model: Dict) -> int:
    """Every held layer's SwiGLU."""
    return (len(model["held"]["layer_kinds"]) * 3 * model["hidden_size"]
            * model["shared_intermediate_size"])


def head_params(model: Dict) -> int:
    return model["hidden_size"] * model["vocab_size"]


def token_matmul_params(model: Dict) -> int:
    """Parameters every token multiplies: each layer's mixer projections
    and SwiGLU, the head."""
    return (count(model, "mamba") * ssm_proj_params(model)
            + count(model, "attention") * attn_proj_params(model)
            + mlp_params(model) + head_params(model))


def attention_flops_fwd(model: Dict, batch: float, seq: int) -> float:
    """QK^T and PV of the attention layers, forward, over the (query, key)
    pairs the causal mask keeps."""
    return (count(model, "attention") * batch
            * model["num_attention_heads"] * 4.0 * model["head_dim"]
            * seq * (seq + 1) / 2.0)


def flash_flops_per_step(model: Dict, batch: float, seq: int) -> float:
    """What the flash kernels of one train step must compute: forward (2
    matmuls) and backward (5), 3.5x the forward, as
    ``flops.flash_flops_per_step``."""
    return 3.5 * attention_flops_fwd(model, batch, seq)


def scan_flops_fwd(model: Dict, batch: float, seq: int) -> float:
    """The chunked scan of one layer, forward (the module's docstring)."""
    H, P, N, G = (model["mamba_n_heads"], model["mamba_d_head"],
                  model["mamba_d_state"], model["mamba_n_groups"])
    Q = min(model["mamba_chunk_size"], seq)
    pairs = Q * (Q + 1) / 2.0
    return batch * seq / Q * (2.0 * pairs * (G * N + H * P)
                              + 4.0 * Q * H * P * N)


def scan_flops_per_step(model: Dict, batch: float, seq: int) -> float:
    """Forward and backward (twice the forward: each product has two
    transposes) of every scan layer."""
    return 3.0 * count(model, "mamba") * scan_flops_fwd(model, batch, seq)


def scan_bytes_per_step(model: Dict, tokens: float, itemsize: int = 2
                        ) -> float:
    """The least bytes every scan layer's scan moves in one train step:
    forward x, B, C and dt in and y out; backward those and dy in, the
    four gradients out."""
    d = ssm_inner(model)
    gn = model["mamba_n_groups"] * model["mamba_d_state"]
    ins = (d + 2 * gn) * itemsize + model["mamba_n_heads"] * 4
    out = d * itemsize
    return count(model, "mamba") * tokens * ((ins + out) + (2 * ins + out))


def conv_bytes_per_step(model: Dict, tokens: float, itemsize: int = 2
                        ) -> float:
    """The least bytes the taps, their bias and the silu move in one train
    step, all scan layers: forward reads x B C and writes it (2 widths a
    token); backward reads it and the output's gradient and writes one
    (3). The taps and their gradient are a few KB."""
    return (count(model, "mamba") * 5 * ssm_conv_dim(model) * itemsize
            * tokens)


def train_flops_per_step(model: Dict, batch: float, seq: int) -> float:
    """6 per matmul parameter and token, attention forward and backward
    (3x the forward, as ``flops.train_flops_per_token``), the scan's."""
    return (6.0 * token_matmul_params(model) * batch * seq
            + 3.0 * attention_flops_fwd(model, batch, seq)
            + scan_flops_per_step(model, batch, seq))


# ---- device time by scope, the scan's scopes among the names

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
    cached = os.path.join(d, "scan_scopes.json")
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


def seconds(obs: Dict[str, Any], names: Tuple[str, ...]) -> Optional[float]:
    """Device seconds of the traced window (mean over chips) under
    ``names``; nothing for a model without scan layers, an untraced run,
    or a program that lacks one of them."""
    t = obs.get("train")
    if not t or not t["traced_steps"] or not is_scan_model(obs):
        return None
    r = for_obs(obs)
    if not r or any(n not in r["scope_self_s"] for n in names):
        return None
    return sum(r["scope_self_s"][n] for n in names)


def chip_tokens(obs: Dict[str, Any]) -> float:
    """One chip's tokens of a step."""
    t = obs["train"]
    return t["tokens_per_step"] / t["chips"]


def percent_of_peak(obs: Dict[str, Any], params: int,
                    busy_s: Optional[float]) -> Optional[float]:
    """Forward and backward of ``params`` matmul parameters for one chip's
    tokens of a step (6 a parameter and token) at the chip's peak, as a
    share of ``busy_s`` device seconds of the traced window per step."""
    return moe_scopes.percent_of_peak(
        obs, 6.0 * params * chip_tokens(obs), busy_s)


def percent_of_floor(obs: Dict[str, Any], flops_per_step: float,
                     bytes_per_step: float, busy_s: Optional[float]
                     ) -> Optional[float]:
    """The larger of ``flops_per_step`` at the chip's peak and
    ``bytes_per_step`` at its HBM bandwidth (one chip's), as a share of
    ``busy_s`` device seconds of the traced window per step."""
    if not busy_s:
        return None
    peak = peaks.peaks(obs["device"]["device_kind"])
    floor_s = max(flops_per_step / peak["bf16_flops"],
                  bytes_per_step / peak["hbm_bytes_per_s"])
    return 100.0 * floor_s / (busy_s / obs["train"]["traced_steps"])
