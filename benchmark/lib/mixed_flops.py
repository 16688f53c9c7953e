"""Operations of a decoder whose layers are of unequal kinds (Laguna),
by layer kind, from shapes; and the reader of a kernel's time by its
exact name.

``model`` holds the Hugging Face names of the configuration file, with
``held`` (the layers this chip holds: ``layer_kinds`` such as
``full+dense``, ``sliding``, ``full``, and their
``num_attention_heads_per_layer``), ``intermediate_size`` the dense MLP's
width, ``moe_intermediate_size`` one expert's, ``sliding_window`` and
``mlp_only_layers``. A token multiplies its layer's projections (the two
head counts differ) and per-head gate, the dense MLP or the router and the
shared expert, and the head; the held experts multiply the rows routed to
them, which is data (the program's counter ``moe_rows_held``). Attention
is causal in a full layer and a band of ``sliding_window`` keys in a
sliding one. Recomputed operations are never counted.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmark.lib import moe_scopes, scopes


def layers(model: Dict) -> List[Dict[str, Any]]:
    """One entry a held layer: its query heads, whether its attention is
    a sliding window, whether its MLP is routed."""
    held = model["held"]
    return [{"heads": heads, "sliding": kind.startswith("sliding"),
             "routed": "dense" not in kind}
            for kind, heads in zip(held["layer_kinds"],
                                   held["num_attention_heads_per_layer"])]


def attn_proj_params(model: Dict, heads: int) -> int:
    """q and output projections of ``heads`` heads, k and v, the per-head
    gate."""
    h, hd = model["hidden_size"], model["head_dim"]
    return (2 * h * heads * hd + 2 * h * model["num_key_value_heads"] * hd
            + h * heads)


def expert_params(model: Dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def attn_proj_params_all(model: Dict) -> int:
    """The projections and gates of every held layer."""
    return sum(attn_proj_params(model, layer["heads"])
               for layer in layers(model))


def mlp_params(model: Dict) -> int:
    """The SwiGLUs every token multiplies: a dense layer's MLP, a routed
    layer's shared expert. Not the router, not the routed experts."""
    h = model["hidden_size"]
    return sum(3 * h * (model["shared_expert_intermediate_size"]
                        if layer["routed"] else model["intermediate_size"])
               for layer in layers(model))


def head_params(model: Dict) -> int:
    return model["hidden_size"] * model["vocab_size"]


def token_matmul_params(model: Dict) -> int:
    """Parameters every token multiplies: projections and gate of every
    layer, the dense MLP or router and shared expert, the head. Not the
    routed experts."""
    routers = (model["hidden_size"] * model["held"]["num_experts_routed_over"]
               * sum(layer["routed"] for layer in layers(model)))
    return (attn_proj_params_all(model) + mlp_params(model) + routers
            + head_params(model))


def attention_flops_fwd(batch: float, seq: int, heads: int, head_dim: int,
                        window: Optional[int] = None) -> float:
    """QK^T and PV, forward, over the (query, key) pairs the mask keeps:
    ``seq (seq + 1) / 2`` causal, with a window ``sum_i min(i + 1,
    window)``."""
    w = seq if window is None else min(window, seq)
    pairs = w * seq - w * (w - 1) / 2.0
    return batch * heads * 4.0 * head_dim * pairs


def flash_flops_per_step(model: Dict, batch: float, seq: int,
                         sliding: bool) -> float:
    """What the flash kernels of one train step must compute in the
    layers of one sort: forward (2 matmuls) and backward (5): 3.5x the
    forward, as ``flops.flash_flops_per_step``."""
    window = model["sliding_window"] if sliding else None
    return 3.5 * sum(
        attention_flops_fwd(batch, seq, layer["heads"], model["head_dim"],
                            window)
        for layer in layers(model) if layer["sliding"] == sliding)


def experts_train_flops(model: Dict, rows_held: float) -> float:
    """Forward and backward FLOPs of the grouped matmuls over the rows
    the held experts multiplied in a step, all routed layers together."""
    return 6.0 * expert_params(model) * rows_held


def train_flops_per_step(model: Dict, batch: float, seq: int,
                         rows_held: float) -> float:
    """6 per matmul parameter and token, 6 per expert parameter and held
    row, attention forward and backward (3x the forward, as
    ``flops.train_flops_per_token``)."""
    attn_fwd = sum(
        attention_flops_fwd(batch, seq, layer["heads"], model["head_dim"],
                            model["sliding_window"] if layer["sliding"]
                            else None)
        for layer in layers(model))
    return (6.0 * token_matmul_params(model) * batch * seq
            + experts_train_flops(model, rows_held) + 3.0 * attn_fwd)


def percent_of_peak_in_scopes(obs: Dict[str, Any], params: int,
                              busy_s: Optional[float]) -> Optional[float]:
    """Forward and backward of ``params`` matmul parameters for one chip's
    tokens of a step (6 a parameter and token) at the chip's peak, as a
    share of ``busy_s`` device seconds of the traced window per step;
    nothing for a model of equal layers or an untraced run."""
    t = obs.get("train")
    if not t or not t["traced_steps"] or "held" not in obs["model"]:
        return None
    return moe_scopes.percent_of_peak(
        obs, 6.0 * params * t["tokens_per_step"] / t["chips"], busy_s)


def percent_of_peak_in_kernels(obs: Dict[str, Any], flops_per_step: float,
                               names: Tuple[str, ...]) -> Optional[float]:
    """``flops_per_step`` at the chip's peak as a share of the device time
    per step of the Mosaic calls named one of ``names``
    (``lib/scopes.py``'s ``kernel_s``), or so named behind the
    transformations jax traced them under (``jvp_jit_gmm__``: a megablox
    call inside the held rows' backward pass); nothing where the program
    has no such call."""
    r = scopes.for_obs(obs)
    got = [v for k, v in (r or {}).get("kernel_s", {}).items()
           if k in names or k.strip("_").endswith(tuple("_" + n
                                                        for n in names))]
    if not got:
        return None
    return moe_scopes.percent_of_peak(obs, flops_per_step, sum(got))
