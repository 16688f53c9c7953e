"""Operations and bytes of a decoder with multi-head latent attention and
a held share of a group-limited routed mixture (DeepSeek-V2), from shapes;
and the device time of its step by the program's own names, latent
attention's among them.

``model`` holds the Hugging Face names of the configuration file
(``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``, ``num_attention_heads`` the heads
this chip holds, ``intermediate_size`` the dense MLP's width,
``moe_intermediate_size`` one expert's, ``n_shared_experts``), with
``held`` (``layer_kinds``: ``mla+dense`` or ``mla``;
``num_experts_routed_over``) and ``vocab_size`` the rows of embedding and
head it holds. A token multiplies its layer's five attention projections,
the dense SwiGLU or the router and the shared experts, and the head; the
held experts multiply the rows routed to them, which is data (the
program's counter ``moe_rows_held``). Recomputed operations and the bytes
they move are never counted.

Attention is causal with keys of ``d_k = qk_nope_head_dim +
qk_rope_head_dim`` and values of ``d_v``: over the ``seq (seq + 1) / 2``
pairs the mask keeps, the forward is ``2 d_k + 2 d_v`` a pair and head
(scores, PV), the flash backward's five products ``3 x 2 d_k + 2 x 2 d_v``
(recomputed scores, dQ and dK at ``d_k``, dP and dV at ``d_v``), so the
kernels' needed work is ``(8 d_k + 6 d_v) / (2 d_k + 2 d_v)`` = 3.6 times
the forward at 192 | 128 (``lib/flops.py``'s 3.5 carried to unequal
widths); the whole step's count takes attention at 3 times its forward,
as ``flops.train_flops_per_token`` does. The kernels' least bytes are q,
k, v, o, dO and the three gradients once each, a key its ``d_n`` dims a
head and the ``d_r`` shared dims once: the same count whatever implements
it, no padding and no broadcast.

``for_obs`` is ``lib/moe_scopes.py``'s reduction with latent attention's
scopes (``mla_q``, ``mla_kv``, ``mla_rope``, ``mla_out``) added to the
names it knows (same plane reader, same self-time rule: the scopes add up
to the device's busy time), cached beside the trace as
``latent_scopes.json``. A program without these scopes gives a reduction
without them, and the readers return nothing.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, Optional, Tuple

from benchmark.lib import moe_scopes, scopes, trace
from benchmark.lib.mixed_flops import experts_train_flops
from benchmark.lib.scan_flops import (chip_tokens,  # noqa: F401
                                      percent_of_floor, percent_of_peak)

MLA_SCOPES = ("mla_q", "mla_kv", "mla_rope", "mla_out")
FLASH_KERNELS = ("flash_kv_fwd", "flash_kv_bwd_dq", "flash_kv_bwd_dkv")
_SCOPE_RE = re.compile(
    r"(?<![A-Za-z0-9_.])("
    + "|".join(scopes.MODEL_SCOPES + moe_scopes.MOE_SCOPES + MLA_SCOPES)
    + r")(?![A-Za-z0-9_.])")


def is_latent_model(obs: Dict[str, Any]) -> bool:
    return "kv_lora_rank" in obs.get("model", ())


def layers(model: Dict) -> int:
    return len(model["held"]["layer_kinds"])


def routed_layers(model: Dict) -> int:
    return sum("dense" not in kind for kind in model["held"]["layer_kinds"])


def _widths(model: Dict) -> Tuple[int, int, int, int]:
    """(held heads, d_n, d_r, d_v)."""
    return (model["num_attention_heads"], model["qk_nope_head_dim"],
            model["qk_rope_head_dim"], model["v_head_dim"])


def mla_proj_params(model: Dict) -> int:
    """One layer's five projections: both down (whole), both up and the
    output (the held heads')."""
    h, rq, rkv = (model["hidden_size"], model["q_lora_rank"],
                  model["kv_lora_rank"])
    H, dn, dr, dv = _widths(model)
    return (h * rq + rq * H * (dn + dr) + h * (rkv + dr)
            + rkv * H * (dn + dv) + H * dv * h)


def shared_width(model: Dict) -> int:
    return model["n_shared_experts"] * model["moe_intermediate_size"]


def mlp_params(model: Dict) -> int:
    """The SwiGLUs every token multiplies: a dense layer's MLP, a routed
    layer's shared experts. Not the router, not the routed experts."""
    h, routed = model["hidden_size"], routed_layers(model)
    return 3 * h * ((layers(model) - routed) * model["intermediate_size"]
                    + routed * shared_width(model))


def head_params(model: Dict) -> int:
    """The held rows of the untied head (the embedding is a gather)."""
    return model["hidden_size"] * model["vocab_size"]


def token_matmul_params(model: Dict) -> int:
    """Parameters every token multiplies: each layer's projections, the
    dense MLP or the router and the shared experts, the head. Not the
    routed experts."""
    routers = (model["hidden_size"] * model["held"]["num_experts_routed_over"]
               * routed_layers(model))
    return (layers(model) * mla_proj_params(model) + mlp_params(model)
            + routers + head_params(model))


def attention_flops_fwd(model: Dict, batch: float, seq: int) -> float:
    """Scores and PV of every layer, forward, over the causal pairs."""
    H, dn, dr, dv = _widths(model)
    return (layers(model) * batch * H * (2.0 * (dn + dr) + 2.0 * dv)
            * seq * (seq + 1) / 2.0)


def flash_flops_per_step(model: Dict, batch: float, seq: int) -> float:
    """What the flash kernels of one train step must compute: the forward's
    two products and the backward's five (the module's docstring)."""
    H, dn, dr, dv = _widths(model)
    return (layers(model) * batch * H * (8.0 * (dn + dr) + 6.0 * dv)
            * seq * (seq + 1) / 2.0)


def flash_bytes_per_step(model: Dict, tokens: float, itemsize: int = 2
                         ) -> float:
    """The least bytes the flash kernels of one train step move: q, k, v
    and o, then dO and the three gradients, once each; the shared rope
    dims of a key once a position, not once a head."""
    H, dn, dr, dv = _widths(model)
    q, k, v = H * (dn + dr), H * dn + dr, H * dv
    return layers(model) * tokens * itemsize * (2 * (q + k + v) + 2 * v)


def train_flops_per_step(model: Dict, batch: float, seq: int,
                         rows_held: float) -> float:
    """6 per matmul parameter and token, 6 per expert parameter and held
    row, attention forward and backward (3x the forward)."""
    return (6.0 * token_matmul_params(model) * batch * seq
            + experts_train_flops(model, rows_held)
            + 3.0 * attention_flops_fwd(model, batch, seq))


# ---- device time by scope, latent attention's scopes among the names

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
    cached = os.path.join(d, "latent_scopes.json")
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
    ``names``; nothing for a model without latent attention, an untraced
    run, or a program that lacks one of them."""
    t = obs.get("train")
    if not t or not t["traced_steps"] or not is_latent_model(obs):
        return None
    r = for_obs(obs)
    if not r or any(n not in r["scope_self_s"] for n in names):
        return None
    return sum(r["scope_self_s"][n] for n in names)
