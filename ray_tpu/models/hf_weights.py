"""Load HuggingFace Llama-family checkpoints into ray_tpu param pytrees.

Reference role: the reference serves/trains models loaded from HF hubs
(e.g. python/ray/llm's engine configs name HF model ids); the TPU-native
equivalent maps the HF state dict onto this repo's stacked-layer pytree:

- torch ``nn.Linear`` stores [out, in] and computes ``x @ W.T``; our
  params store [in, out] and compute ``x @ W`` — every projection
  transposes on import.
- per-layer tensors stack along a leading layer axis (the model scans
  over it; pipeline parallelism shards it).
- rotary embeddings are split-half (GPT-NeoX convention) in BOTH
  implementations, so no head permutation is needed.

Use ``llama_from_hf`` with a transformers model, a state dict, or a
checkpoint path (anything ``LlamaForCausalLM.from_pretrained`` accepts).
Logit parity with the HF implementation is asserted in
tests/test_models.py.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def _parse_rope_scaling(hf_cfg):
    """llama3 / linear / yarn rope scaling are implemented
    (ops/layers.rope_frequencies); every other type refuses loudly —
    silently-wrong logits are worse than a load error."""
    scaling = getattr(hf_cfg, "rope_scaling", None)
    if not scaling:
        return None
    rope_type = scaling.get("rope_type") or scaling.get("type")
    if rope_type not in ("llama3", "linear", "yarn"):
        raise ValueError(
            f"unsupported HF config: rope_scaling type {rope_type!r} "
            f"(implemented: 'llama3', 'linear', 'yarn')")
    scaling = dict(scaling)
    if rope_type == "yarn" and not scaling.get(
            "original_max_position_embeddings"):
        # transformers falls back to the FIXED config length; pinning it
        # here keeps inv_freq identical across prefill/decode/training
        # table lengths (rope_frequencies would otherwise see each
        # call's max_seq_len)
        scaling["original_max_position_embeddings"] = \
            hf_cfg.max_position_embeddings
    return tuple(sorted(
        (k, v) for k, v in scaling.items() if v is not None))


def llama_config_from_hf(hf_cfg, attn_qkv_bias: bool = False) -> "Any":
    from ray_tpu.models.llama import LlamaConfig

    rope_scaling = _parse_rope_scaling(hf_cfg)
    if not attn_qkv_bias and (getattr(hf_cfg, "attention_bias", False)
                              or getattr(hf_cfg, "mlp_bias", False)):
        raise ValueError(
            "unsupported HF config: attention_bias/mlp_bias checkpoints "
            "carry bias tensors this model has no slots for")
    return LlamaConfig(
        attn_qkv_bias=attn_qkv_bias,
        vocab_size=hf_cfg.vocab_size,
        hidden_size=hf_cfg.hidden_size,
        intermediate_size=hf_cfg.intermediate_size,
        num_layers=hf_cfg.num_hidden_layers,
        num_heads=hf_cfg.num_attention_heads,
        num_kv_heads=getattr(hf_cfg, "num_key_value_heads", None)
        or hf_cfg.num_attention_heads,
        head_dim=getattr(hf_cfg, "head_dim", None),
        max_seq_len=hf_cfg.max_position_embeddings,
        rope_theta=float(getattr(hf_cfg, "rope_theta", 10000.0)),
        rms_norm_eps=float(hf_cfg.rms_norm_eps),
        tie_embeddings=bool(getattr(hf_cfg, "tie_word_embeddings", False)),
        rope_scaling=rope_scaling,
    )


def _fetcher(state_dict):
    """(t, lin): fetch-as-numpy, and torch-Linear-transposed fetch."""
    import numpy as np

    def t(name):
        v = state_dict[name]
        if hasattr(v, "detach"):
            v = v.detach().to("cpu").float().numpy()
        return np.asarray(v)

    def lin(name):  # torch Linear [out, in] -> ours [in, out]
        return t(name).T

    return t, lin


def _refuse_proj_bias(state_dict):
    bias_keys = [k for k in state_dict
                 if k.endswith(("proj.bias",)) and "layers" in k]
    if bias_keys:
        raise ValueError(
            f"unsupported checkpoint: projection bias tensors present "
            f"(e.g. {bias_keys[0]}) — this model implements bias-free "
            f"projections")


def _stack_attn(stacked, t, lin, prefix):
    """The llama-style attention block shared by Llama and Mixtral."""
    stacked["attn_norm"].append(t(prefix + "input_layernorm.weight"))
    stacked["wq"].append(lin(prefix + "self_attn.q_proj.weight"))
    stacked["wk"].append(lin(prefix + "self_attn.k_proj.weight"))
    stacked["wv"].append(lin(prefix + "self_attn.v_proj.weight"))
    stacked["wo"].append(lin(prefix + "self_attn.o_proj.weight"))
    stacked["mlp_norm"].append(
        t(prefix + "post_attention_layernorm.weight"))


def _assemble(cfg, stacked, t, lin, dtype):
    import numpy as np

    import jax.numpy as jnp

    params = {
        "embed": jnp.asarray(t("model.embed_tokens.weight"), dtype),
        "layers": {k: jnp.asarray(np.stack(v), dtype)
                   for k, v in stacked.items()},
        "final_norm": jnp.asarray(t("model.norm.weight"), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jnp.asarray(lin("lm_head.weight"), dtype)
    return params


def llama_params_from_hf(state_dict: Dict[str, Any], cfg,
                         dtype=None) -> Dict[str, Any]:
    """HF Llama state dict (torch tensors or numpy) -> param pytree."""
    dtype = dtype or cfg.param_dtype
    t, lin = _fetcher(state_dict)
    _refuse_proj_bias(state_dict)
    stacked: Dict[str, list] = {k: [] for k in (
        "attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
        "w_up", "w_down")}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        _stack_attn(stacked, t, lin, p)
        stacked["w_gate"].append(lin(p + "mlp.gate_proj.weight"))
        stacked["w_up"].append(lin(p + "mlp.up_proj.weight"))
        stacked["w_down"].append(lin(p + "mlp.down_proj.weight"))
    return _assemble(cfg, stacked, t, lin, dtype)


def gpt2_from_hf(source, dtype=None) -> Tuple[Any, Dict[str, Any]]:
    """(cfg, params) from a transformers GPT2LMHeadModel (or a checkpoint
    path/model id). GPT-2's HF weights use Conv1D layout [in, out] — the
    same orientation this repo uses, so tensors map 1:1 with only the
    per-layer stacking."""
    import numpy as np

    import jax.numpy as jnp

    from ray_tpu.models.gpt2 import GPT2Config

    if isinstance(source, str):
        from transformers import GPT2LMHeadModel

        source = GPT2LMHeadModel.from_pretrained(source)
    hf_cfg = source.config
    cfg = GPT2Config(vocab_size=hf_cfg.vocab_size,
                     hidden_size=hf_cfg.n_embd,
                     num_layers=hf_cfg.n_layer,
                     num_heads=hf_cfg.n_head,
                     max_seq_len=hf_cfg.n_positions,
                     ln_eps=float(hf_cfg.layer_norm_epsilon))
    if dtype is not None:
        from dataclasses import replace

        cfg = replace(cfg, param_dtype=dtype)
    sd = source.state_dict()
    t, _ = _fetcher(sd)

    names = {"ln1_g": "ln_1.weight", "ln1_b": "ln_1.bias",
             "w_qkv": "attn.c_attn.weight", "b_qkv": "attn.c_attn.bias",
             "w_proj": "attn.c_proj.weight", "b_proj": "attn.c_proj.bias",
             "ln2_g": "ln_2.weight", "ln2_b": "ln_2.bias",
             "w_fc": "mlp.c_fc.weight", "b_fc": "mlp.c_fc.bias",
             "w_out": "mlp.c_proj.weight", "b_out": "mlp.c_proj.bias"}
    pd = cfg.param_dtype if dtype is None else dtype
    layers = {ours: jnp.asarray(np.stack(
        [t(f"transformer.h.{i}.{hf}") for i in range(cfg.num_layers)]), pd)
        for ours, hf in names.items()}
    params = {
        "wte": jnp.asarray(t("transformer.wte.weight"), pd),
        "wpe": jnp.asarray(t("transformer.wpe.weight"), pd),
        "layers": layers,
        "lnf_g": jnp.asarray(t("transformer.ln_f.weight"), pd),
        "lnf_b": jnp.asarray(t("transformer.ln_f.bias"), pd),
    }
    return cfg, params


def llama_from_hf(source, dtype=None) -> Tuple[Any, Dict[str, Any]]:
    """(cfg, params) from a transformers model instance or a checkpoint
    path/model id loadable by ``LlamaForCausalLM.from_pretrained``."""
    if isinstance(source, str):
        from transformers import LlamaForCausalLM

        source = LlamaForCausalLM.from_pretrained(source)
    cfg = llama_config_from_hf(source.config)
    if dtype is not None:
        from dataclasses import replace

        cfg = replace(cfg, param_dtype=dtype)
    return cfg, llama_params_from_hf(source.state_dict(), cfg, dtype=dtype)


def _moe_config_kwargs(hf_cfg, num_experts: int, dtype) -> Dict[str, Any]:
    """What the Mixtral and OLMoE configs share with each other."""
    return dict(
        **({} if dtype is None else {"param_dtype": dtype}),
        vocab_size=hf_cfg.vocab_size,
        hidden_size=hf_cfg.hidden_size,
        intermediate_size=hf_cfg.intermediate_size,
        num_layers=hf_cfg.num_hidden_layers,
        num_heads=hf_cfg.num_attention_heads,
        num_kv_heads=hf_cfg.num_key_value_heads,
        head_dim=getattr(hf_cfg, "head_dim", None),
        max_seq_len=hf_cfg.max_position_embeddings,
        rope_theta=float(hf_cfg.rope_theta),
        rms_norm_eps=float(hf_cfg.rms_norm_eps),
        tie_embeddings=bool(getattr(hf_cfg, "tie_word_embeddings", False)),
        num_experts=num_experts,
        top_k=hf_cfg.num_experts_per_tok,
        rope_scaling=_parse_rope_scaling(hf_cfg),
    )


def _moe_params_from_hf(source, cfg, router: str, expert: str,
                        names: Tuple[str, str, str], extra=()):
    """Stacked [L, E, ...] expert tensors from per-expert linears.
    ``router`` and ``expert`` are the key templates under a layer
    (``{e}`` the expert), ``names`` the gate, up and down linears,
    ``extra`` further per-layer vectors as (ours, theirs)."""
    import numpy as np

    sd = source.state_dict()
    t, lin = _fetcher(sd)
    _refuse_proj_bias(sd)
    keys = ("e_gate", "e_up", "e_down")
    stacked: Dict[str, list] = {k: [] for k in (
        "attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "router") + keys
        + tuple(o for o, _ in extra)}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        _stack_attn(stacked, t, lin, p)
        stacked["router"].append(lin(p + router))
        for ours, theirs in zip(keys, names):
            stacked[ours].append(np.stack(
                [lin(p + expert.format(e=e) + theirs + ".weight")
                 for e in range(cfg.num_experts)]))
        for ours, theirs in extra:
            stacked[ours].append(t(p + theirs))
    return _assemble(cfg, stacked, t, lin, cfg.param_dtype)


def mixtral_from_hf(source, dtype=None) -> Tuple[Any, Dict[str, Any]]:
    """(cfg, params) from a transformers MixtralForCausalLM (or a
    checkpoint path/model id). Experts map w1->e_gate, w3->e_up,
    w2->e_down (Mixtral's naming), stacked [L, E, ...]. The routed layer
    is dropless, as transformers' is, so the logits agree exactly."""
    from ray_tpu.models.mixtral import MixtralConfig

    if isinstance(source, str):
        from transformers import MixtralForCausalLM

        source = MixtralForCausalLM.from_pretrained(source)
    hf_cfg = source.config
    sw = getattr(hf_cfg, "sliding_window", None)
    if sw is not None and sw < hf_cfg.max_position_embeddings:
        raise ValueError(
            f"unsupported HF config: sliding_window={sw} (this model "
            f"implements full causal attention only; sequences past the "
            f"window would silently diverge from HF)")
    cfg = MixtralConfig(
        **_moe_config_kwargs(hf_cfg, hf_cfg.num_local_experts, dtype))
    return cfg, _moe_params_from_hf(
        source, cfg, "block_sparse_moe.gate.weight",
        "block_sparse_moe.experts.{e}.", ("w1", "w3", "w2"))


def olmoe_from_hf(source, dtype=None) -> Tuple[Any, Dict[str, Any]]:
    """(cfg, params) from a transformers OlmoeForCausalLM (or a
    checkpoint path/model id): Mixtral's expert layout under OLMoE's
    names, plus the q and k norms."""
    from ray_tpu.models.olmoe import OlmoeConfig

    if isinstance(source, str):
        from transformers import OlmoeForCausalLM

        source = OlmoeForCausalLM.from_pretrained(source)
    hf_cfg = source.config
    if getattr(hf_cfg, "clip_qkv", None) is not None:
        raise ValueError("unsupported HF config: clip_qkv is set (this "
                         "model does not clamp q, k and v)")
    cfg = OlmoeConfig(
        **_moe_config_kwargs(hf_cfg, hf_cfg.num_experts, dtype),
        norm_topk_prob=bool(hf_cfg.norm_topk_prob),
        router_aux_coef=float(hf_cfg.router_aux_loss_coef))
    return cfg, _moe_params_from_hf(
        source, cfg, "mlp.gate.weight", "mlp.experts.{e}.",
        ("gate_proj", "up_proj", "down_proj"),
        extra=(("q_norm", "self_attn.q_norm.weight"),
               ("k_norm", "self_attn.k_norm.weight")))


def qwen2_from_hf(source, dtype=None) -> Tuple[Any, Dict[str, Any]]:
    """(cfg, params) from a transformers Qwen2ForCausalLM (or checkpoint
    path/model id). Qwen2 IS the llama block plus additive q/k/v biases
    (cfg.attn_qkv_bias), so the mapping is llama's + three bias stacks;
    o_proj/mlp remain bias-free and anything else refuses."""
    if isinstance(source, str):
        from transformers import Qwen2ForCausalLM

        source = Qwen2ForCausalLM.from_pretrained(source)
    hf_cfg = source.config
    sw = getattr(hf_cfg, "sliding_window", None)
    if getattr(hf_cfg, "use_sliding_window", False) and sw is not None \
            and sw < hf_cfg.max_position_embeddings:
        raise ValueError(
            f"unsupported HF config: sliding_window={sw} (full causal "
            f"attention only)")
    from dataclasses import replace

    cfg = llama_config_from_hf(hf_cfg, attn_qkv_bias=True)
    if dtype is not None:
        cfg = replace(cfg, param_dtype=dtype)
    sd = source.state_dict()
    bad = [k for k in sd if k.endswith(("o_proj.bias", "gate_proj.bias",
                                        "up_proj.bias", "down_proj.bias"))]
    if bad:
        raise ValueError(
            f"unsupported checkpoint: unexpected bias {bad[0]} (qwen2 "
            f"carries biases on q/k/v only)")
    t, lin = _fetcher(sd)
    pd = cfg.param_dtype
    stacked: Dict[str, list] = {k: [] for k in (
        "attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
        "w_up", "w_down", "bq", "bk", "bv")}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        _stack_attn(stacked, t, lin, p)
        stacked["bq"].append(t(p + "self_attn.q_proj.bias"))
        stacked["bk"].append(t(p + "self_attn.k_proj.bias"))
        stacked["bv"].append(t(p + "self_attn.v_proj.bias"))
        stacked["w_gate"].append(lin(p + "mlp.gate_proj.weight"))
        stacked["w_up"].append(lin(p + "mlp.up_proj.weight"))
        stacked["w_down"].append(lin(p + "mlp.down_proj.weight"))
    return cfg, _assemble(cfg, stacked, t, lin, pd)


def gemma_from_hf(source, dtype=None) -> Tuple[Any, Dict[str, Any]]:
    """(cfg, params) from a transformers GemmaForCausalLM (or checkpoint
    path/model id). Gemma's deltas from the llama block, all absorbed
    here: GeGLU gate activation (cfg.mlp_act="gelu_tanh"), embeddings
    scaled by sqrt(hidden) at lookup (cfg.embed_scale), (1+w) RMSNorm —
    folded into the stored norm weights so the model code stays llama's
    — tied lm_head, and an explicit head_dim (256 on gemma-7b).
    Reference serves gemma via external engines; here it rides the same
    train/decode paths as llama."""
    import math as _math

    if isinstance(source, str):
        from transformers import GemmaForCausalLM

        source = GemmaForCausalLM.from_pretrained(source)
    hf_cfg = source.config
    from dataclasses import replace as _replace

    from ray_tpu.models.llama import LlamaConfig

    act = getattr(hf_cfg, "hidden_activation", None) or getattr(
        hf_cfg, "hidden_act", "gelu_pytorch_tanh")
    try:
        # "gelu" is transformers' EXACT erf GELU, not the tanh approx —
        # conflating them breaks parity at ~1e-3
        mlp_act = {"gelu_pytorch_tanh": "gelu_tanh", "gelu": "gelu"}[act]
    except KeyError:
        raise ValueError(
            f"unsupported gemma hidden activation {act!r}") from None
    cfg = LlamaConfig(
        vocab_size=hf_cfg.vocab_size,
        hidden_size=hf_cfg.hidden_size,
        intermediate_size=hf_cfg.intermediate_size,
        num_layers=hf_cfg.num_hidden_layers,
        num_heads=hf_cfg.num_attention_heads,
        num_kv_heads=getattr(hf_cfg, "num_key_value_heads", None)
        or hf_cfg.num_attention_heads,
        head_dim=getattr(hf_cfg, "head_dim", None),
        max_seq_len=hf_cfg.max_position_embeddings,
        rope_theta=float(getattr(hf_cfg, "rope_theta", 10000.0)),
        rms_norm_eps=float(hf_cfg.rms_norm_eps),
        tie_embeddings=True,  # gemma always ties lm_head to embeddings
        mlp_act=mlp_act,
        embed_scale=float(_math.sqrt(hf_cfg.hidden_size)),
    )
    if dtype is not None:
        cfg = _replace(cfg, param_dtype=dtype)
    state_dict = source.state_dict()
    t, lin = _fetcher(state_dict)
    _refuse_proj_bias(state_dict)
    stacked: Dict[str, list] = {k: [] for k in (
        "attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
        "w_up", "w_down")}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        _stack_attn(stacked, t, lin, p)
        stacked["w_gate"].append(lin(p + "mlp.gate_proj.weight"))
        stacked["w_up"].append(lin(p + "mlp.up_proj.weight"))
        stacked["w_down"].append(lin(p + "mlp.down_proj.weight"))
    params = _assemble(cfg, stacked, t, lin, dtype or cfg.param_dtype)
    # gemma RMSNorm computes normed * (1 + w): fold the +1 in here so
    # ops/layers.rms_norm (normed * w) is exact
    params["layers"]["attn_norm"] = params["layers"]["attn_norm"] + 1
    params["layers"]["mlp_norm"] = params["layers"]["mlp_norm"] + 1
    params["final_norm"] = params["final_norm"] + 1
    return cfg, params


def hf_model_type(source) -> str:
    """The checkpoint's ``model_type`` WITHOUT loading weights (config
    only for a path/id) — callers can refuse unsupported architectures
    before paying a multi-GB download/instantiation."""
    if isinstance(source, str):
        from transformers import AutoConfig

        return AutoConfig.from_pretrained(source).model_type
    return source.config.model_type


def from_hf(source, dtype=None) -> Tuple[Any, Dict[str, Any]]:
    """Architecture-dispatching loader: llama / qwen2 / mixtral / olmoe / gpt2
    by the checkpoint's ``model_type`` (reference role: engines resolve
    HF ids via AutoConfig). Accepts a model instance or a path/id."""
    if isinstance(source, str):
        from transformers import AutoConfig

        model_type = AutoConfig.from_pretrained(source).model_type
    else:
        model_type = source.config.model_type
    loader = {"llama": llama_from_hf, "qwen2": qwen2_from_hf,
              "gemma": gemma_from_hf,
              "mixtral": mixtral_from_hf, "olmoe": olmoe_from_hf,
              "gpt2": gpt2_from_hf}.get(
        model_type)
    if loader is None:
        raise ValueError(
            f"unsupported HF model_type {model_type!r} "
            f"(implemented: llama, qwen2, gemma, mixtral, olmoe, gpt2)")
    return loader(source, dtype=dtype)
