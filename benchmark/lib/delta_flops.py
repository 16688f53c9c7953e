"""Operations and bytes of a decoder whose layers mix gated delta-rule
layers with full attention (Olmo-Hybrid), by layer kind, from shapes; and
the device time of its step by the program's own names, the rule's among
them.

``model`` holds the Hugging Face names of the configuration file
(``linear_num_value_heads``, ``linear_key_head_dim``,
``linear_value_head_dim``, ``linear_conv_kernel_dim``), with
``held["layer_kinds"]`` the layers this chip holds (``linear`` or
``full``) and ``vocab_size`` the rows of embedding and head it holds. A
token multiplies its layer's mixer (a linear layer's in- and
out-projection, or attention's four), the layer's SwiGLU and the untied
head. Attention is causal. Recomputed operations and the bytes they move
are never counted.

The rule's operations are the chunked (WY / UT) algorithm's at the
published implementation's chunk of 64 (``RULE_CHUNK``; the recurrence
token by token needs ``7 V K`` a position and head, within a fifth of
it), a chunk of ``C`` positions and a head at a time, over the ``C (C + 1)
/ 2`` pairs a triangle keeps: ``K K^T``, ``Q K^T`` and ``T`` against ``K``
(``2 K`` a pair each), ``T`` against ``V`` and the masked scores against
``V'`` (``2 V`` a pair each), the triangular inverse (``C^3 / 3``), and
the three products with the carried state, ``W S^T``, ``Q S^T`` and ``V'^T
K`` (``2 C K V`` each). Its least bytes are what it must read and write
once: q, k, v, a and b (the activations' dtype) in, o out; the backward
reads those and ``do`` and writes the five gradients.

``for_obs`` is ``lib/scan_flops.py``'s reduction with the rule's scopes
(``gdn``, ``gdn_in``, ``gdn_conv``, ``gdn_rule``, ``gdn_norm``,
``gdn_out``) in place of the scan's (same plane reader, same self-time
rule: the scopes add up to the device's busy time), cached beside the
trace as ``delta_scopes.json``. A program without these scopes gives a
reduction without them, and the readers return nothing.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, Optional, Tuple

from benchmark.lib import scopes, trace
from benchmark.lib.scan_flops import (chip_tokens,  # noqa: F401
                                      percent_of_floor, percent_of_peak)

RULE_CHUNK = 64
GDN_SCOPES = ("gdn", "gdn_in", "gdn_conv", "gdn_rule", "gdn_norm", "gdn_out")
DELTA_MODEL_SCOPES = scopes.MODEL_SCOPES + GDN_SCOPES
_SCOPE_RE = re.compile(
    r"(?<![A-Za-z0-9_.])(" + "|".join(DELTA_MODEL_SCOPES)
    + r")(?![A-Za-z0-9_.])")


def is_delta_model(obs: Dict[str, Any]) -> bool:
    return "linear_key_head_dim" in obs.get("model", ())


def count(model: Dict, kind: str) -> int:
    """Held layers of ``kind`` (``linear`` or ``full``)."""
    return model["held"]["layer_kinds"].count(kind)


def _hkv(model: Dict) -> Tuple[int, int, int]:
    return (model["linear_num_value_heads"], model["linear_key_head_dim"],
            model["linear_value_head_dim"])


def conv_dim(model: Dict) -> int:
    """The channels the taps run over: q, k and v."""
    H, K, V = _hkv(model)
    return H * (2 * K + V)


def gdn_proj_params(model: Dict) -> int:
    """One linear layer's in-projection (to the gate, q k v, a and b) and
    out-projection."""
    H, _, V = _hkv(model)
    h = model["hidden_size"]
    return h * (H * V + conv_dim(model) + 2 * H) + H * V * h


def attn_proj_params(model: Dict) -> int:
    """One full layer's q and output projections, k and v."""
    h, hd = model["hidden_size"], model["head_dim"]
    return (2 * h * model["num_attention_heads"] * hd
            + 2 * h * model["num_key_value_heads"] * hd)


def mlp_params(model: Dict) -> int:
    """Every held layer's SwiGLU."""
    return (len(model["held"]["layer_kinds"]) * 3 * model["hidden_size"]
            * model["intermediate_size"])


def head_params(model: Dict) -> int:
    """The held rows of the untied head (the embedding is a gather)."""
    return model["hidden_size"] * model["vocab_size"]


def token_matmul_params(model: Dict) -> int:
    """Parameters every token multiplies: each layer's mixer projections
    and SwiGLU, the head."""
    return (count(model, "linear") * gdn_proj_params(model)
            + count(model, "full") * attn_proj_params(model)
            + mlp_params(model) + head_params(model))


def attention_flops_fwd(model: Dict, batch: float, seq: int) -> float:
    """QK^T and PV of the full layers, forward, over the (query, key)
    pairs the causal mask keeps."""
    return (count(model, "full") * batch * model["num_attention_heads"]
            * 4.0 * model["head_dim"] * seq * (seq + 1) / 2.0)


def flash_flops_per_step(model: Dict, batch: float, seq: int) -> float:
    """What the flash kernels of one train step must compute: forward (2
    matmuls) and backward (5), 3.5x the forward, as
    ``flops.flash_flops_per_step``."""
    return 3.5 * attention_flops_fwd(model, batch, seq)


def rule_flops_fwd(model: Dict, batch: float, seq: int) -> float:
    """The chunked rule of one layer, forward (the module's docstring)."""
    H, K, V = _hkv(model)
    C = min(RULE_CHUNK, seq)
    pairs = C * (C + 1) / 2.0
    return batch * seq / C * H * (pairs * (6.0 * K + 4.0 * V)
                                  + C ** 3 / 3.0 + 6.0 * C * K * V)


def rule_flops_per_step(model: Dict, batch: float, seq: int) -> float:
    """Forward and backward (twice the forward: each product has two
    transposes) of every linear layer."""
    return 3.0 * count(model, "linear") * rule_flops_fwd(model, batch, seq)


def rule_bytes_per_step(model: Dict, tokens: float, itemsize: int = 2
                        ) -> float:
    """The least bytes every linear layer's rule moves in one train step:
    forward q, k, v, a and b in and o out; backward those and do in, the
    five gradients out."""
    H, _, V = _hkv(model)
    ins = (conv_dim(model) + 2 * H) * itemsize
    out = H * V * itemsize
    return count(model, "linear") * tokens * ((ins + out) + (2 * ins + out))


def conv_bytes_per_step(model: Dict, tokens: float, itemsize: int = 2
                        ) -> float:
    """The least bytes the taps and the silu move in one train step, all
    linear layers: forward reads q k v and writes it (2 widths a token);
    backward reads it and the output's gradient and writes one (3). The
    taps and their gradient are a few KB."""
    return count(model, "linear") * 5 * conv_dim(model) * itemsize * tokens


def train_flops_per_step(model: Dict, batch: float, seq: int) -> float:
    """6 per matmul parameter and token, attention forward and backward
    (3x the forward, as ``flops.train_flops_per_token``), the rule's."""
    return (6.0 * token_matmul_params(model) * batch * seq
            + 3.0 * attention_flops_fwd(model, batch, seq)
            + rule_flops_per_step(model, batch, seq))


# ---- device time by scope, the rule's scopes among the names

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
    cached = os.path.join(d, "delta_scopes.json")
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
    ``names``; nothing for a model without delta-rule layers, an untraced
    run, or a program that lacks one of them."""
    t = obs.get("train")
    if not t or not t["traced_steps"] or not is_delta_model(obs):
        return None
    r = for_obs(obs)
    if not r or any(n not in r["scope_self_s"] for n in names):
        return None
    return sum(r["scope_self_s"][n] for n in names)
