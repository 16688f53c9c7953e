"""Operations and bytes of a decoder whose mixers are Kimi Delta Attention
layers (a delta rule whose decay is a vector over the key's channels) among
gated latent-attention layers with no query latent, dense SwiGLUs first and
then a routed mixture with a held share beside one shared expert
(Ling-3.0-flash), by part, from shapes; and the device time of its step by
the program's own names.

``model`` holds the names of the configuration file (``hidden_size``,
``num_attention_heads`` and ``head_dim``: a KDA layer's heads and their key
and value widths; ``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``; ``intermediate_size``,
``moe_intermediate_size``, ``moe_shared_expert_intermediate_size``), with
``held["layer_kinds"]`` the layers this chip holds (``kda+dense``,
``kda+moe``, ``mla+moe``), ``held["num_experts_routed_over"]`` the router's
outputs and ``vocab_size`` the rows of embedding and head it holds. A token
multiplies its layer's mixer (a KDA layer's in-projection to q, k, v, the
decay's f, beta's b and the output gate, and its out-projection; a latent
layer's ``W_q``, ``W_kva``, ``W_kvb``, ``W_g`` and ``W_o``), a dense layer's
SwiGLU or a routed layer's router and shared expert, and the untied head;
the held experts multiply the rows routed to them, which is data (the
program's counter ``moe_rows_held``). Attention is causal. Recomputed
operations and the bytes they move are never counted.

The work is counted whatever implements it. The rule's operations are
``lib/delta_flops.py``'s chunked algorithm at a chunk of 64 (``K K^T``,
``Q K^T``, ``T`` against ``K`` and ``V``, the masked scores against ``V'``,
the triangular inverse and the three products with the carried state): the
decays, a vector a position, add no product. Its least bytes read q, k, v,
the decay ``[s, H K]`` and beta once in the activations' dtype and write o;
the backward reads those and ``do`` and writes the five gradients.

``for_obs`` is ``lib/delta_moe_flops.py``'s reduction with this model's
scopes (same plane reader, same self-time rule: the scopes add up to the
device's busy time), cached beside the trace as ``kda_moe_scopes.json``. An
operation goes to its innermost scope. A program without these scopes gives
a reduction without them, and the readers return nothing.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, Optional, Tuple

from benchmark.lib import delta_flops, moe_scopes, scopes, trace
from benchmark.lib.scan_flops import (chip_tokens,  # noqa: F401
                                      percent_of_floor, percent_of_peak)

KDA_SCOPES = ("kda", "kda_in", "kda_conv", "kda_gate", "kda_rule",
              "kda_norm", "kda_out", "kda_pre_norm")
MLA_SCOPES = ("mla_q", "mla_kv", "mla_rope", "mla_out")
FLASH_KERNELS = ("flash_kv_fwd", "flash_kv_bwd_dq", "flash_kv_bwd_dkv")
ALL_SCOPES = (scopes.MODEL_SCOPES + moe_scopes.MOE_SCOPES + KDA_SCOPES
              + MLA_SCOPES + ("attn_gate", "moe_shared"))
_SCOPE_RE = re.compile(
    r"(?<![A-Za-z0-9_.])(" + "|".join(ALL_SCOPES) + r")(?![A-Za-z0-9_.])")


def is_kda_moe_model(obs: Dict[str, Any]) -> bool:
    m = obs.get("model", ())
    return ("kda_lower_bound" in m and "moe_intermediate_size" in m
            and "layer_kinds" in m.get("held", ()))


def count(model: Dict, part: str) -> int:
    """Held layers one of whose parts is ``part`` (``kda``, ``mla``,
    ``dense`` or ``moe``)."""
    return sum(part in kind.split("+")
               for kind in model["held"]["layer_kinds"])


def _hkv(model: Dict) -> Tuple[int, int, int]:
    """(heads, key size, value size) of a KDA layer."""
    return (model["num_attention_heads"], model["head_dim"],
            model["head_dim"])


def conv_dim(model: Dict) -> int:
    """The channels the taps run over: q, k and v."""
    H, K, V = _hkv(model)
    return H * (2 * K + V)


def kda_proj_params(model: Dict) -> int:
    """One KDA layer's in-projection (to q k v, the decay's f, beta's b and
    the output gate) and out-projection."""
    H, K, V = _hkv(model)
    h = model["hidden_size"]
    return h * (conv_dim(model) + H * K + 2 * H) + H * V * h


def _widths(model: Dict) -> Tuple[int, int, int, int]:
    return (model["num_attention_heads"], model["qk_nope_head_dim"],
            model["qk_rope_head_dim"], model["v_head_dim"])


def mla_proj_params(model: Dict) -> int:
    """One latent layer's projections with no query latent: ``W_q``,
    ``W_kva``, ``W_kvb``, the head-wise gate's ``W_g`` and ``W_o``."""
    h, rkv = model["hidden_size"], model["kv_lora_rank"]
    H, dn, dr, dv = _widths(model)
    return (h * H * (dn + dr) + h * (rkv + dr) + rkv * H * (dn + dv)
            + h * H + H * dv * h)


def shared_params(model: Dict) -> int:
    """One routed layer's shared expert: gate, up, down."""
    return (3 * model["hidden_size"]
            * model["moe_shared_expert_intermediate_size"])


def dense_params(model: Dict) -> int:
    return 3 * model["hidden_size"] * model["intermediate_size"]


def router_params(model: Dict) -> int:
    return model["hidden_size"] * model["held"]["num_experts_routed_over"]


def expert_params(model: Dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def head_params(model: Dict) -> int:
    """The held rows of the untied head (the embedding is a gather)."""
    return model["hidden_size"] * model["vocab_size"]


def token_matmul_params(model: Dict) -> int:
    """Parameters every token multiplies: each layer's mixer projections, a
    dense layer's SwiGLU, a routed layer's router and shared expert, the
    head. Not the routed experts."""
    return (count(model, "kda") * kda_proj_params(model)
            + count(model, "mla") * mla_proj_params(model)
            + count(model, "dense") * dense_params(model)
            + count(model, "moe") * (router_params(model)
                                     + shared_params(model))
            + head_params(model))


def attention_flops_fwd(model: Dict, batch: float, seq: int) -> float:
    """Scores and PV of the latent layers, forward, over the causal
    pairs."""
    H, dn, dr, dv = _widths(model)
    return (count(model, "mla") * batch * H * (2.0 * (dn + dr) + 2.0 * dv)
            * seq * (seq + 1) / 2.0)


def flash_flops_per_step(model: Dict, batch: float, seq: int) -> float:
    """The flash kernels' forward (two products) and backward (five), as
    ``lib/latent_flops.flash_flops_per_step``."""
    H, dn, dr, dv = _widths(model)
    return (count(model, "mla") * batch * H * (8.0 * (dn + dr) + 6.0 * dv)
            * seq * (seq + 1) / 2.0)


def flash_bytes_per_step(model: Dict, tokens: float, itemsize: int = 2
                         ) -> float:
    """q, k, v and o, then dO and the three gradients, once each; the
    shared rope dims of a key once a position."""
    H, dn, dr, dv = _widths(model)
    q, k, v = H * (dn + dr), H * dn + dr, H * dv
    return (count(model, "mla") * tokens * itemsize
            * (2 * (q + k + v) + 2 * v))


def rule_flops_fwd(model: Dict, batch: float, seq: int) -> float:
    """The chunked rule of one layer, forward (``lib/delta_flops.py``'s
    count at this model's heads; the decays add no product)."""
    H, K, V = _hkv(model)
    C = min(delta_flops.RULE_CHUNK, seq)
    pairs = C * (C + 1) / 2.0
    return batch * seq / C * H * (pairs * (6.0 * K + 4.0 * V)
                                  + C ** 3 / 3.0 + 6.0 * C * K * V)


def rule_flops_per_step(model: Dict, batch: float, seq: int) -> float:
    """Forward and backward (twice the forward) of every KDA layer."""
    return 3.0 * count(model, "kda") * rule_flops_fwd(model, batch, seq)


def rule_bytes_per_step(model: Dict, tokens: float, itemsize: int = 2
                        ) -> float:
    """The least bytes every KDA layer's rule moves in one train step:
    forward q, k, v, the decay a channel and beta in and o out; backward
    those and do in, the five gradients out."""
    H, K, V = _hkv(model)
    ins = (conv_dim(model) + H * K + H) * itemsize
    out = H * V * itemsize
    return count(model, "kda") * tokens * ((ins + out) + (2 * ins + out))


def conv_bytes_per_step(model: Dict, tokens: float, itemsize: int = 2
                        ) -> float:
    """The least bytes the taps and the silu move in one train step, all
    KDA layers: forward 2 widths of q k v a token, backward 3."""
    return count(model, "kda") * 5 * conv_dim(model) * itemsize * tokens


def experts_train_flops(model: Dict, rows_held: float) -> float:
    return 6.0 * expert_params(model) * rows_held


def train_flops_per_step(model: Dict, batch: float, seq: int,
                         rows_held: float) -> float:
    """6 per matmul parameter and token, 6 per expert parameter and held
    row, attention forward and backward (3x the forward, as
    ``flops.train_flops_per_token``), the rule's."""
    return (6.0 * token_matmul_params(model) * batch * seq
            + experts_train_flops(model, rows_held)
            + 3.0 * attention_flops_fwd(model, batch, seq)
            + rule_flops_per_step(model, batch, seq))


# ---- device time by scope

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
    cached = os.path.join(d, "kda_moe_scopes.json")
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
            also: Tuple[str, ...] = ()) -> Optional[float]:
    """Device seconds of the traced window (mean over chips) under
    ``names``, and under those of ``also`` that the trace has; nothing for
    another model, an untraced run, or a program that lacks one of
    ``names``."""
    t = obs.get("train")
    if not t or not t["traced_steps"] or not is_kda_moe_model(obs):
        return None
    r = for_obs(obs)
    if not r or any(n not in r["scope_self_s"] for n in names):
        return None
    return sum(r["scope_self_s"].get(n, 0.0) for n in names + also)
