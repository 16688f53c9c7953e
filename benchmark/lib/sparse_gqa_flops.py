"""Operations and bytes of a decoder whose every layer attends, with
grouped-query heads, over the keys a learned index chooses, under a rope in
three position streams, with a held share of a routed mixture and no shared
expert (Keye-VL-2.0-30B-A3B), from shapes; and the device time of its step
by the program's own names, ``mrope`` among them.

``model`` holds the Hugging Face names of the configuration file:
``num_attention_heads`` query heads on ``num_key_value_heads`` key/value
heads of ``head_dim``; ``sa_config`` (``indexer_num_heads``,
``indexer_head_dim``, ``topk``); ``moe_intermediate_size`` one expert's
width, ``num_local_experts`` the router's outputs, ``num_hidden_layers`` the
layers held and ``vocab_size`` the rows of embedding and head held.
Recomputed operations and the bytes they move are never counted, and every
count is of the work the equations need whatever implements it, so that no
share of a roofline can pass 100% (``lib/sparse_flops.py`` says the rules:
pairs, the index's scores forward over every causal pair and backward over
the chosen ones, 4 a parameter and token for the index's projections):

- **attention** over the chosen pairs: forward ``2 d + 2 d`` a pair and
  query head, the flash backward's five products ``3 x 2 d + 2 x 2 d``:
  ``8 d + 6 d`` a pair and head, 32 heads; the whole step counts attention
  at 3 times its forward. Least bytes: q and o at the 32 heads, k and v
  ONCE A GROUP (4 heads' worth, not 32), then dO, dq and the group's dk, dv
  once each.

``for_obs`` is ``lib/sparse_flops.py``'s reduction with ``mrope`` added to
the names it knows, cached beside the trace as ``sparse_gqa_scopes.json``.
A program without these scopes gives a reduction without them, and the
readers return nothing.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, Optional, Tuple

from benchmark.lib import moe_scopes, scopes, sparse_flops, trace
from benchmark.lib.mixed_flops import experts_train_flops
from benchmark.lib.scan_flops import (chip_tokens,  # noqa: F401
                                      percent_of_floor, percent_of_peak)
from benchmark.lib.sparse_flops import causal_pairs, kept_pairs  # noqa: F401

PROJ_SCOPES = ("attn_qkv", "attn_out", "dsa_proj")
_SCOPE_RE = re.compile(
    r"(?<![A-Za-z0-9_.])("
    + "|".join(scopes.MODEL_SCOPES + moe_scopes.MOE_SCOPES
               + sparse_flops.DSA_SCOPES + ("mrope",))
    + r")(?![A-Za-z0-9_.])")


def is_sparse_gqa_model(obs: Dict[str, Any]) -> bool:
    return "sa_config" in obs.get("model", ())


def layers(model: Dict) -> int:
    return model["num_hidden_layers"]


def _heads(model: Dict) -> Tuple[int, int, int]:
    """(query heads, key/value heads, head size)."""
    return (model["num_attention_heads"], model["num_key_value_heads"],
            model["head_dim"])


def _index(model: Dict) -> Tuple[int, int, int]:
    """(index heads, their size, the keys a query keeps)."""
    sa = model["sa_config"]
    return sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]


def attn_proj_params(model: Dict) -> int:
    """One layer's four projections: wq and wo at the query heads, wk and
    wv at the key/value heads."""
    H, G, d = _heads(model)
    return model["hidden_size"] * d * (2 * H + 2 * G)


def index_proj_params(model: Dict) -> int:
    """A layer's index: queries, one key and the head weights, all from
    the layer's normed input."""
    J, di, _ = _index(model)
    return model["hidden_size"] * (J * di + di + J)


def proj_flops_per_step(model: Dict, tokens: float) -> float:
    """Forward and backward of every layer's attention projections (6 a
    parameter and token) and of the index's (4: no input gradient)."""
    return tokens * layers(model) * (6.0 * attn_proj_params(model)
                                     + 4.0 * index_proj_params(model))


def index_flops_per_step(model: Dict, batch: float, seq: int) -> float:
    """What the index's scores need in one train step: every causal pair
    forward, the chosen pairs backward."""
    J, di, topk = _index(model)
    return layers(model) * batch * 2.0 * J * di * (
        causal_pairs(seq) + 2.0 * kept_pairs(seq, topk))


def index_bytes_per_step(model: Dict, tokens: float, itemsize: int = 2
                         ) -> float:
    """Queries, keys and head weights read, their gradients written."""
    J, di, _ = _index(model)
    return layers(model) * tokens * 2 * ((J * di + di) * itemsize + J * 4)


def sparse_flash_flops_per_step(model: Dict, batch: float, seq: int
                                ) -> float:
    """Attention over the chosen keys, forward and backward, every layer:
    chosen pairs x query heads x (8 d + 6 d)."""
    H, _, d = _heads(model)
    return (layers(model) * batch * H * (8.0 * d + 6.0 * d)
            * kept_pairs(seq, _index(model)[2]))


def flash_bytes_per_step(model: Dict, tokens: float, itemsize: int = 2
                         ) -> float:
    """The least bytes the attention moves in a train step: q, k, v and o,
    then dO and the three gradients, once each, keys and values (and their
    gradients) once a GROUP."""
    H, G, d = _heads(model)
    q, kv = H * d, 2 * G * d
    return layers(model) * tokens * itemsize * (2 * (q + kv) + 2 * q)


def head_params(model: Dict) -> int:
    """The held rows of the untied head (the embedding is a gather)."""
    return model["hidden_size"] * model["vocab_size"]


def router_params(model: Dict) -> int:
    return model["hidden_size"] * model["num_local_experts"] * layers(model)


def train_flops_per_step(model: Dict, batch: float, seq: int,
                         rows_held: float) -> float:
    """The whole step's needed FLOPs: 6 per matmul parameter and token (4
    for the index's projections), 6 per expert parameter and held row, the
    index's scores, attention over the chosen pairs forward and backward
    (3x the forward's ``4 d`` a pair and head)."""
    H, _, d = _heads(model)
    return (proj_flops_per_step(model, batch * seq)
            + 6.0 * (router_params(model) + head_params(model)) * batch * seq
            + experts_train_flops(model, rows_held)
            + index_flops_per_step(model, batch, seq)
            + 3.0 * layers(model) * batch * H * 4.0 * d
            * kept_pairs(seq, _index(model)[2]))


# ---- device time by scope, ``mrope`` among the names

def scope_of(path: str) -> str:
    found = _SCOPE_RE.findall(path.split(";", 1)[0])
    return found[-1] if found else "unscoped"


def reduce_scopes(xplane_path: str) -> Dict[str, Any]:
    """``lib/sparse_flops.reduce_scopes`` with this module's names."""
    planes = scopes.read_planes(xplane_path)
    planes.pop("/host:CPU", None)
    chips = [(p, ops) for p, ops in (
        (planes[name], [ev for ln in planes[name]["lines"]
                        if ln["name"] == "XLA Ops" for ev in ln["events"]])
        for name in sorted(planes)) if ops]
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
    cached = os.path.join(d, "sparse_gqa_scopes.json")
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
    of ``names`` the trace has; nothing for another model, an untraced
    run, or a program that lacks one of ``need`` (all of ``names``, unless
    given)."""
    t = obs.get("train")
    if not t or not t["traced_steps"] or not is_sparse_gqa_model(obs):
        return None
    r = for_obs(obs)
    need = names if need is None else need
    if not r or any(n not in r["scope_self_s"] for n in need):
        return None
    return sum(r["scope_self_s"].get(n, 0.0) for n in names)


def share_of_busy(obs: Dict[str, Any], names: Tuple[str, ...]
                  ) -> Optional[float]:
    """Percent of the device's busy time under ``names``."""
    busy = seconds(obs, names)
    r = for_obs(obs) if busy is not None else None
    if not r or not r["busy_s"]:
        return None
    return 100.0 * busy / r["busy_s"]
