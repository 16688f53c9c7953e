"""Operations and bytes of a decoder whose layers are one part each
(Mamba-2 scans at grouped heads, mixtures of two-matrix experts in a latent
with a held share beside a squared-ReLU shared expert, attention without
rotation) with a multi-token prediction module beside the head
(``nemotron_h``), by part, from shapes; and the device time of its step by
the program's own names.

``model`` holds the Hugging Face names of the configuration file
(``mamba_num_heads``, ``mamba_head_dim``, ``ssm_state_size``, ``n_groups``,
``conv_kernel``, ``chunk_size``, ``moe_latent_size``,
``moe_intermediate_size``, ``moe_shared_expert_intermediate_size``), with
``held["layer_kinds"]`` the layers this chip holds (``mamba``, ``moe`` or
``attention``), ``held["mtp_layer_kinds"]`` the prediction module's,
``held["num_experts_routed_over"]`` the router's outputs and ``vocab_size``
the rows of embedding and head it holds. A token multiplies its layer's one
part (a scan's in- and out-projection; attention's four; a mixture's
router, both latent projections and the shared expert's two matrices), the
module's joining matrix and layers, and the head twice (the model's and the
module's pass); the held experts multiply the rows routed to them, which is
data (the program's counter ``moe_rows_held``). Attention is causal.
Recomputed operations and the bytes they move are never counted, and the
work is counted whatever implements it.

``for_obs`` is ``lib/scan_flops.py``'s reduction with the mixture's, the
latent's and the module's scopes beside the scan's (same plane reader, same
self-time rule: the scopes add up to the device's busy time), cached beside
the trace as ``scan_moe_scopes.json``. An operation goes to its innermost
scope, and an operation anywhere under the scope ``mtp`` to ``"mtp/" + its
innermost scope`` (``mtp/flash``, ``mtp/moe_route``, ``mtp/head_loss`` for
the module's pass of the head): what lies under ``mtp`` adds up to the
module's time. A program without these scopes gives a reduction without
them, and the readers return nothing.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, Optional, Tuple

from benchmark.lib import moe_scopes, scan_flops, scopes, trace
from benchmark.lib.scan_flops import (chip_tokens,  # noqa: F401
                                      percent_of_floor, percent_of_peak)

MODULE = "mtp"
EXTRA_SCOPES = ("moe_shared", "moe_latent", "moe_bias_update", "mtp_join",
                "mtp_head", MODULE)
ALL_SCOPES = (scan_flops.SCAN_MODEL_SCOPES + moe_scopes.MOE_SCOPES
              + EXTRA_SCOPES)
_SCOPE_RE = re.compile(
    r"(?<![A-Za-z0-9_.])(" + "|".join(ALL_SCOPES) + r")(?![A-Za-z0-9_.])")


def is_scan_moe_model(obs: Dict[str, Any]) -> bool:
    m = obs.get("model", ())
    return ("moe_latent_size" in m and "mamba_num_heads" in m
            and "mtp_layer_kinds" in m.get("held", ()))


def count(model: Dict, kind: str, module: Optional[bool] = None) -> int:
    """Held layers of ``kind``: the stack's and the module's, the module's
    alone (``module=True``) or the stack's alone (``False``)."""
    held = model["held"]
    return ((0 if module else held["layer_kinds"].count(kind))
            + (0 if module is False else held["mtp_layer_kinds"].count(kind)))


def ssm_inner(model: Dict) -> int:
    return model["mamba_num_heads"] * model["mamba_head_dim"]


def ssm_conv_dim(model: Dict) -> int:
    """The channels the taps run over: x, and B and C in their groups."""
    return ssm_inner(model) + 2 * model["n_groups"] * model["ssm_state_size"]


def ssm_proj_params(model: Dict) -> int:
    """One scan layer's in-projection (to z, x B C and dt) and
    out-projection."""
    h, d = model["hidden_size"], ssm_inner(model)
    return h * (d + ssm_conv_dim(model) + model["mamba_num_heads"]) + d * h


def attn_proj_params(model: Dict) -> int:
    h, hd = model["hidden_size"], model["head_dim"]
    return (2 * h * model["num_attention_heads"] * hd
            + 2 * h * model["num_key_value_heads"] * hd)


def latent_params(model: Dict) -> int:
    """One mixture's down- and up-projection."""
    return 2 * model["hidden_size"] * model["moe_latent_size"]


def shared_params(model: Dict) -> int:
    """One mixture's shared expert: two matrices around a squared ReLU."""
    return (2 * model["hidden_size"]
            * model["moe_shared_expert_intermediate_size"])


def router_params(model: Dict) -> int:
    return model["hidden_size"] * model["held"]["num_experts_routed_over"]


def expert_params(model: Dict) -> int:
    """One routed expert: two matrices in the latent."""
    return 2 * model["moe_latent_size"] * model["moe_intermediate_size"]


def head_params(model: Dict) -> int:
    """The held rows of the untied head, one pass."""
    return model["hidden_size"] * model["vocab_size"]


def join_params(model: Dict) -> int:
    return 2 * model["hidden_size"] ** 2


def mixture_params(model: Dict) -> int:
    """What every token multiplies in one mixture, outside its experts."""
    return router_params(model) + latent_params(model) + shared_params(model)


def module_matmul_params(model: Dict) -> int:
    """Parameters every token multiplies in the prediction module: the
    joining matrix, its layers, its pass of the head."""
    return (join_params(model)
            + count(model, "attention", True) * attn_proj_params(model)
            + count(model, "moe", True) * mixture_params(model)
            + count(model, "mamba", True) * ssm_proj_params(model)
            + head_params(model))


def token_matmul_params(model: Dict) -> int:
    """Parameters every token multiplies: each layer's one part, the head,
    the module. Not the routed experts."""
    return (count(model, "mamba", False) * ssm_proj_params(model)
            + count(model, "attention", False) * attn_proj_params(model)
            + count(model, "moe", False) * mixture_params(model)
            + head_params(model) + module_matmul_params(model))


def attention_flops_fwd(model: Dict, batch: float, seq: int,
                        module: Optional[bool] = None) -> float:
    """QK^T and PV of the attention layers, forward, over the (query, key)
    pairs the causal mask keeps."""
    return (count(model, "attention", module) * batch
            * model["num_attention_heads"] * 4.0 * model["head_dim"]
            * seq * (seq + 1) / 2.0)


def flash_flops_per_step(model: Dict, batch: float, seq: int) -> float:
    """Forward (2 matmuls) and backward (5) of the flash kernels: 3.5x the
    forward, as ``flops.flash_flops_per_step``; the stack's layer and the
    module's."""
    return 3.5 * attention_flops_fwd(model, batch, seq)


def scan_flops_fwd(model: Dict, batch: float, seq: int) -> float:
    """The chunked scan of one layer, forward (``lib/scan_flops.py``'s
    count at this model's groups: a chunk's ``C B^T`` once a group)."""
    H, P, N, G = (model["mamba_num_heads"], model["mamba_head_dim"],
                  model["ssm_state_size"], model["n_groups"])
    Q = min(model["chunk_size"], seq)
    pairs = Q * (Q + 1) / 2.0
    return batch * seq / Q * (2.0 * pairs * (G * N + H * P)
                              + 4.0 * Q * H * P * N)


def scan_flops_per_step(model: Dict, batch: float, seq: int) -> float:
    return 3.0 * count(model, "mamba") * scan_flops_fwd(model, batch, seq)


def scan_bytes_per_step(model: Dict, tokens: float, itemsize: int = 2
                        ) -> float:
    """The least bytes every scan layer's scan moves in one train step
    (``lib/scan_flops.scan_bytes_per_step`` at this model's widths)."""
    d = ssm_inner(model)
    gn = model["n_groups"] * model["ssm_state_size"]
    ins = (d + 2 * gn) * itemsize + model["mamba_num_heads"] * 4
    out = d * itemsize
    return count(model, "mamba") * tokens * ((ins + out) + (2 * ins + out))


def conv_bytes_per_step(model: Dict, tokens: float, itemsize: int = 2
                        ) -> float:
    return count(model, "mamba") * 5 * ssm_conv_dim(model) * itemsize * tokens


def experts_train_flops(model: Dict, rows_held: float) -> float:
    """Forward and backward of the grouped matmuls over the rows the held
    experts multiplied in a step, all mixtures together: two matrices of
    ``latent x width`` a row."""
    return 6.0 * expert_params(model) * rows_held


def train_flops_per_step(model: Dict, batch: float, seq: int,
                         rows_held: float) -> float:
    """6 per matmul parameter and token, 6 per expert parameter and held
    row, attention forward and backward (3x the forward), the scans'."""
    return (6.0 * token_matmul_params(model) * batch * seq
            + experts_train_flops(model, rows_held)
            + 3.0 * attention_flops_fwd(model, batch, seq)
            + scan_flops_per_step(model, batch, seq))


def module_flops_per_step(model: Dict, batch: float, seq: int,
                          rows_held_module: float) -> float:
    """What the prediction module needs of a step: its matmuls, its
    attention's scores, its held experts' rows, its pass of the head."""
    return (6.0 * module_matmul_params(model) * batch * seq
            + experts_train_flops(model, rows_held_module)
            + 3.0 * attention_flops_fwd(model, batch, seq, module=True))


# ---- device time by scope

def scope_of(path: str) -> str:
    found = _SCOPE_RE.findall(path.split(";", 1)[0])
    if not found:
        return "unscoped"
    if MODULE in found:
        inner = [n for n in found if n != MODULE]
        return MODULE + "/" + (inner[-1] if inner else MODULE)
    return found[-1]


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
    cached = os.path.join(d, "scan_moe_scopes.json")
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
    ``names`` and under those of ``also`` that the trace has, in the stack
    and in the module (``mtp/<name>``) together; nothing for another model,
    an untraced run, or a program that lacks one of ``names``."""
    t = obs.get("train")
    if not t or not t["traced_steps"] or not is_scan_moe_model(obs):
        return None
    r = for_obs(obs)
    if not r:
        return None
    by = r["scope_self_s"]
    if any(n not in by and MODULE + "/" + n not in by for n in names):
        return None
    return sum(by.get(n, 0.0) + by.get(MODULE + "/" + n, 0.0)
               for n in names + also)


def module_seconds(obs: Dict[str, Any]) -> Optional[float]:
    """Device seconds of the traced window under the scope ``mtp``."""
    t = obs.get("train")
    if not t or not t["traced_steps"] or not is_scan_moe_model(obs):
        return None
    r = for_obs(obs)
    under = [v for k, v in (r or {"scope_self_s": {}})["scope_self_s"].items()
             if k.startswith(MODULE + "/")]
    return sum(under) if under else None
