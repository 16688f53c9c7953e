"""Operations and bytes of a decoder whose full layers attend over the
keys a learned index chooses and whose window layers are latent attention
of their own ranks, with a held share of a routed mixture
(dots3-note-prev), from shapes, by layer kind; and the device time of its
step by the program's own names, the index's among them.

``model`` holds the Hugging Face names of the configuration file (the
full layers' ``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim`` and ``num_attention_heads`` the heads
this chip holds; the same under ``swa_`` for the window layers with
``sliding_window_size``; ``index_n_heads``, ``index_head_dim``,
``index_topk``; ``intermediate_size`` the dense MLP's width,
``moe_intermediate_size`` one expert's, ``n_shared_experts``), with
``held`` (``layer_kinds``: ``full+dense``, ``full`` or ``sliding``;
``num_experts_routed_over``) and ``vocab_size`` the rows of embedding and
head it holds. Recomputed operations and the bytes they move are never
counted, and every count is of the work the equations need whatever
implements it, so that no share of a roofline can pass 100%:

- a position's **pairs**: a full layer's query ``t`` meets ``min(t + 1,
  index_topk)`` chosen keys of its ``t + 1`` causal ones, a window layer's
  ``min(t + 1, sliding_window_size)``;
- the **index's scores**: ``2 J d_i`` a causal pair forward (every causal
  pair must be scored before any can be dropped); backward, the gradient
  of ``L_I`` is zero off the chosen pairs, so ``dq_i`` and ``dk_i`` are
  ``2 x 2 J d_i`` a CHOSEN pair. The index's three projections take no
  input gradient (``u`` and ``c_q`` under ``stop_gradient``): 4 a
  parameter and token, not 6;
- **attention** over the pairs a layer keeps, keys ``d_k = d_n + d_r``,
  values ``d_v``: forward ``2 d_k + 2 d_v`` a pair and head, the flash
  backward's five products ``3 x 2 d_k + 2 x 2 d_v``; the kernels' needed
  work ``8 d_k + 6 d_v`` a pair and head, the whole step's count attention
  at 3 times its forward (``lib/latent_flops.py``); least bytes q, k, v, o,
  dO and the three gradients once each, the shared rope dims once a
  position.

``for_obs`` is ``lib/latent_flops.py``'s reduction with the index's and the
gate's scopes added to the names it knows, cached beside the trace as
``sparse_scopes.json``. A program without these scopes gives a reduction
without them, and the readers return nothing.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, Optional, Tuple

from benchmark.lib import latent_flops, moe_scopes, scopes, trace
from benchmark.lib.mixed_flops import experts_train_flops
from benchmark.lib.scan_flops import (chip_tokens,  # noqa: F401
                                      percent_of_floor, percent_of_peak)

DSA_SCOPES = ("dsa_proj", "dsa_scores", "dsa_select", "flash_sparse",
              "dsa_loss", "flash_window", "attn_gate", "moe_shared",
              "moe_bias_update")
PROJ_SCOPES = latent_flops.MLA_SCOPES + ("attn_gate", "dsa_proj")
WINDOW_KERNELS = latent_flops.FLASH_KERNELS
_SCOPE_RE = re.compile(
    r"(?<![A-Za-z0-9_.])("
    + "|".join(scopes.MODEL_SCOPES + moe_scopes.MOE_SCOPES
               + latent_flops.MLA_SCOPES + DSA_SCOPES)
    + r")(?![A-Za-z0-9_.])")


def is_sparse_model(obs: Dict[str, Any]) -> bool:
    return "index_topk" in obs.get("model", ())


def kinds(model: Dict) -> Tuple[str, ...]:
    return tuple(model["held"]["layer_kinds"])


def full_layers(model: Dict) -> int:
    return sum(k.startswith("full") for k in kinds(model))


def window_layers(model: Dict) -> int:
    return sum(k.startswith("sliding") for k in kinds(model))


def routed_layers(model: Dict) -> int:
    return sum("dense" not in k for k in kinds(model))


def _widths(model: Dict, prefix: str = "") -> Tuple[int, int, int, int]:
    """(held heads, d_n, d_r, d_v) of a kind of layer."""
    return (model[prefix + "num_attention_heads"],
            model[prefix + "qk_nope_head_dim"],
            model[prefix + "qk_rope_head_dim"], model[prefix + "v_head_dim"])


def mla_proj_params(model: Dict, prefix: str = "") -> int:
    """One layer's five projections (both down whole, both up and the
    output at the held heads) and its head gate."""
    h, rq, rkv = (model["hidden_size"], model[prefix + "q_lora_rank"],
                  model[prefix + "kv_lora_rank"])
    H, dn, dr, dv = _widths(model, prefix)
    return (h * rq + rq * H * (dn + dr) + h * (rkv + dr)
            + rkv * H * (dn + dv) + H * dv * h + h * H)


def index_proj_params(model: Dict) -> int:
    """A full layer's index: queries from the query latent, one key and
    the head weights from the layer's input. Not divided over chips."""
    J, di = model["index_n_heads"], model["index_head_dim"]
    return (model["q_lora_rank"] * J * di + model["hidden_size"] * di
            + model["hidden_size"] * J)


def proj_flops_per_step(model: Dict, tokens: float) -> float:
    """Forward and backward of every layer's attention projections and
    gate (6 a parameter and token) and of the index's (4)."""
    return tokens * (
        6.0 * (full_layers(model) * mla_proj_params(model)
               + window_layers(model) * mla_proj_params(model, "swa_"))
        + 4.0 * full_layers(model) * index_proj_params(model))


def causal_pairs(seq: int) -> float:
    return seq * (seq + 1) / 2.0


def kept_pairs(seq: int, keep: int) -> float:
    """``sum_t min(t + 1, keep)`` over a sequence's positions."""
    k = min(seq, keep)
    return k * (k + 1) / 2.0 + (seq - k) * keep


def index_flops_per_step(model: Dict, batch: float, seq: int) -> float:
    """What the index's scores need in one train step (the module's
    docstring): every causal pair forward, the chosen pairs backward."""
    J, di = model["index_n_heads"], model["index_head_dim"]
    return full_layers(model) * batch * 2.0 * J * di * (
        causal_pairs(seq) + 2.0 * kept_pairs(seq, model["index_topk"]))


def index_bytes_per_step(model: Dict, tokens: float, itemsize: int = 2
                         ) -> float:
    """Queries, keys and head weights read, their gradients written."""
    J, di = model["index_n_heads"], model["index_head_dim"]
    return full_layers(model) * tokens * 2 * (
        (J * di + di) * itemsize + J * 4)


def _attention(model: Dict, batch: float, seq: int, per_pair) -> float:
    """``per_pair(d_k, d_v)`` FLOPs a pair and head, over both kinds."""
    H, dn, dr, dv = _widths(model)
    Hw, wn, wr, wv = _widths(model, "swa_")
    return batch * (
        full_layers(model) * H * per_pair(dn + dr, dv)
        * kept_pairs(seq, model["index_topk"])
        + window_layers(model) * Hw * per_pair(wn + wr, wv)
        * kept_pairs(seq, model["sliding_window_size"]))


def sparse_flash_flops_per_step(model: Dict, batch: float, seq: int
                                ) -> float:
    """Attention over the chosen keys, forward and backward, full layers."""
    H, dn, dr, dv = _widths(model)
    return (full_layers(model) * batch * H * (8.0 * (dn + dr) + 6.0 * dv)
            * kept_pairs(seq, model["index_topk"]))


def window_flash_flops_per_step(model: Dict, batch: float, seq: int
                                ) -> float:
    """Attention over the band, forward and backward, window layers."""
    H, dn, dr, dv = _widths(model, "swa_")
    return (window_layers(model) * batch * H * (8.0 * (dn + dr) + 6.0 * dv)
            * kept_pairs(seq, model["sliding_window_size"]))


def flash_bytes_per_step(model: Dict, tokens: float, prefix: str = "",
                         itemsize: int = 2) -> float:
    """The least bytes the attention of one kind's layers moves in a train
    step: q, k, v and o, then dO and the three gradients, once each; the
    shared rope dims of a key once a position."""
    H, dn, dr, dv = _widths(model, prefix)
    n = window_layers(model) if prefix else full_layers(model)
    q, k, v = H * (dn + dr), H * dn + dr, H * dv
    return n * tokens * itemsize * (2 * (q + k + v) + 2 * v)


def shared_width(model: Dict) -> int:
    return model["n_shared_experts"] * model["moe_intermediate_size"]


def mlp_params(model: Dict) -> int:
    """The SwiGLUs every token multiplies: a dense layer's MLP, a routed
    layer's shared expert. Not the router, not the routed experts."""
    h, routed = model["hidden_size"], routed_layers(model)
    return 3 * h * ((len(kinds(model)) - routed) * model["intermediate_size"]
                    + routed * shared_width(model))


def head_params(model: Dict) -> int:
    """The held rows of the untied head (the embedding is a gather)."""
    return model["hidden_size"] * model["vocab_size"]


def router_params(model: Dict) -> int:
    return (model["hidden_size"] * model["held"]["num_experts_routed_over"]
            * routed_layers(model))


def train_flops_per_step(model: Dict, batch: float, seq: int,
                         rows_held: float) -> float:
    """The whole step's needed FLOPs: 6 per matmul parameter and token (4
    for the index's projections), 6 per expert parameter and held row, the
    index's scores, attention over the kept pairs forward and backward (3x
    the forward)."""
    return (proj_flops_per_step(model, batch * seq)
            + 6.0 * (mlp_params(model) + router_params(model)
                     + head_params(model)) * batch * seq
            + experts_train_flops(model, rows_held)
            + index_flops_per_step(model, batch, seq)
            + 3.0 * _attention(model, batch, seq,
                               lambda dk, dv: 2.0 * dk + 2.0 * dv))


# ---- device time by scope, the index's scopes among the names

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
    cached = os.path.join(d, "sparse_scopes.json")
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
            need: Optional[Tuple[str, ...]] = None) -> Optional[float]:
    """Device seconds of the traced window (mean over chips) under those
    of ``names`` the trace has; nothing for a model without an index, an
    untraced run, or a program that lacks one of ``need`` (all of
    ``names``, unless given)."""
    t = obs.get("train")
    if not t or not t["traced_steps"] or not is_sparse_model(obs):
        return None
    r = for_obs(obs)
    need = names if need is None else need
    if not r or any(n not in r["scope_self_s"] for n in need):
        return None
    return sum(r["scope_self_s"].get(n, 0.0) for n in names)
