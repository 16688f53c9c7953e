"""Operations and bytes of a decoder whose layers are gated delta-rule
layers with fewer key heads than value heads among gated full-attention
layers, a routed mixture with a held share beside a gated shared expert in
every layer (Qwen3-Next), by part, from shapes; and the device time of its
step by the program's own names.

``model`` holds the Hugging Face names of the configuration file
(``linear_num_key_heads``, ``linear_num_value_heads``,
``linear_key_head_dim``, ``linear_value_head_dim``,
``moe_intermediate_size``, ``shared_expert_intermediate_size``), with
``held["layer_kinds"]`` the layers this chip holds (``linear`` or
``full``), ``held["num_experts_routed_over"]`` the router's outputs and
``vocab_size`` the rows of embedding and head it holds. A token multiplies
its layer's mixer (a linear layer's in- and out-projection; a full layer's
``wq`` at twice its heads' width, the gate's half with it, ``wk``, ``wv``
and ``wo``), the router, the shared expert with its gate's vector, and the
untied head; the held experts multiply the rows routed to them, which is
data (the program's counter ``moe_rows_held``). Attention is causal.
Recomputed operations and the bytes they move are never counted.

The work is counted whatever implements it. The rule's operations are
``lib/delta_flops.py``'s chunked algorithm at the value heads (every value
head has a state and a ``K K^T`` of its own, though two share a key). Its
least bytes read q and k once at the *key* heads (a kernel that handed a
key head to its value heads would read no more; the program's copies of q
and k at the value heads are not needed bytes), v, a and b at the value
heads, and write o; the backward reads those and ``do`` and writes the five
gradients at the same widths.

``for_obs`` is ``lib/delta_flops.py``'s reduction with the mixture's and
the gates' scopes beside the rule's (same plane reader, same self-time
rule: the scopes add up to the device's busy time), cached beside the trace
as ``delta_moe_scopes.json``. An operation goes to its innermost scope:
``moe_shared_gate`` lies in ``moe_shared``, ``attn_gate`` in ``attn_qkv``
or ``attn_out``, and a reader sums what it means. A program without these
scopes gives a reduction without them, and the readers return nothing.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, Optional, Tuple

from benchmark.lib import delta_flops, moe_scopes, scopes, trace
from benchmark.lib.scan_flops import (chip_tokens,  # noqa: F401
                                      percent_of_floor, percent_of_peak)

EXTRA_SCOPES = ("gdn_pre_norm", "attn_gate", "moe_shared", "moe_shared_gate")
ALL_SCOPES = (delta_flops.DELTA_MODEL_SCOPES + moe_scopes.MOE_SCOPES
              + EXTRA_SCOPES)
_SCOPE_RE = re.compile(
    r"(?<![A-Za-z0-9_.])(" + "|".join(ALL_SCOPES) + r")(?![A-Za-z0-9_.])")


def is_delta_moe_model(obs: Dict[str, Any]) -> bool:
    m = obs.get("model", ())
    return ("linear_num_key_heads" in m and "moe_intermediate_size" in m
            and "layer_kinds" in m.get("held", ()))


count = delta_flops.count


def _heads(model: Dict) -> Tuple[int, int, int, int]:
    """(key heads, value heads, key size, value size) of a linear layer."""
    return (model["linear_num_key_heads"], model["linear_num_value_heads"],
            model["linear_key_head_dim"], model["linear_value_head_dim"])


def conv_dim(model: Dict) -> int:
    """The channels the taps run over: q and k at the key heads, v at the
    value heads."""
    Hk, Hv, K, V = _heads(model)
    return 2 * Hk * K + Hv * V


def gdn_proj_params(model: Dict) -> int:
    """One linear layer's in-projection (to the gate z, q k v, a and b)
    and out-projection."""
    _, Hv, _, V = _heads(model)
    h = model["hidden_size"]
    return h * (Hv * V + conv_dim(model) + 2 * Hv) + Hv * V * h


def attn_proj_params(model: Dict) -> int:
    """One full layer's ``wq`` (each head's query and its gate), ``wo``,
    ``wk`` and ``wv``."""
    h, hd = model["hidden_size"], model["head_dim"]
    return (3 * h * model["num_attention_heads"] * hd
            + 2 * h * model["num_key_value_heads"] * hd)


def shared_params(model: Dict) -> int:
    """One layer's shared expert: gate, up, down, and its gate's vector."""
    h = model["hidden_size"]
    return 3 * h * model["shared_expert_intermediate_size"] + h


def router_params(model: Dict) -> int:
    return model["hidden_size"] * model["held"]["num_experts_routed_over"]


def expert_params(model: Dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def head_params(model: Dict) -> int:
    """The held rows of the untied head (the embedding is a gather)."""
    return model["hidden_size"] * model["vocab_size"]


def token_matmul_params(model: Dict) -> int:
    """Parameters every token multiplies: each layer's mixer projections,
    router and shared expert, the head. Not the routed experts."""
    layers = len(model["held"]["layer_kinds"])
    return (count(model, "linear") * gdn_proj_params(model)
            + count(model, "full") * attn_proj_params(model)
            + layers * (router_params(model) + shared_params(model))
            + head_params(model))


def attention_flops_fwd(model: Dict, batch: float, seq: int) -> float:
    """QK^T and PV of the full layers, forward, over the (query, key)
    pairs the causal mask keeps."""
    return (count(model, "full") * batch * model["num_attention_heads"]
            * 4.0 * model["head_dim"] * seq * (seq + 1) / 2.0)


def flash_flops_per_step(model: Dict, batch: float, seq: int) -> float:
    """Forward (2 matmuls) and backward (5) of the flash kernels: 3.5x the
    forward, as ``flops.flash_flops_per_step``."""
    return 3.5 * attention_flops_fwd(model, batch, seq)


def rule_flops_per_step(model: Dict, batch: float, seq: int) -> float:
    """``lib/delta_flops.rule_flops_per_step``: forward and backward of
    the chunked rule at the value heads, every linear layer."""
    return delta_flops.rule_flops_per_step(model, batch, seq)


def rule_bytes_per_step(model: Dict, tokens: float, itemsize: int = 2
                        ) -> float:
    """The least bytes every linear layer's rule moves in one train step
    (the module's docstring): q and k at the key heads."""
    _, Hv, _, V = _heads(model)
    ins = (conv_dim(model) + 2 * Hv) * itemsize
    out = Hv * V * itemsize
    return count(model, "linear") * tokens * ((ins + out) + (2 * ins + out))


def conv_bytes_per_step(model: Dict, tokens: float, itemsize: int = 2
                        ) -> float:
    """The least bytes the taps and the silu move in one train step, all
    linear layers (``lib/delta_flops.conv_bytes_per_step`` at this model's
    channels)."""
    return count(model, "linear") * 5 * conv_dim(model) * itemsize * tokens


def experts_train_flops(model: Dict, rows_held: float) -> float:
    """Forward and backward of the grouped matmuls over the rows the held
    experts multiplied in a step, all layers together."""
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
    cached = os.path.join(d, "delta_moe_scopes.json")
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
    if not t or not t["traced_steps"] or not is_delta_moe_model(obs):
        return None
    r = for_obs(obs)
    if not r or any(n not in r["scope_self_s"] for n in names):
        return None
    return sum(r["scope_self_s"].get(n, 0.0) for n in names + also)
