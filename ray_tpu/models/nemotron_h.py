"""Nemotron-H (nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16,
``nemotron_h``): layers of one part each, Mamba-2 scans and LatentMoE
mixtures in turn with an attention layer every period, and a multi-token
prediction module beside the head.

``h0 = embed[tokens]``; every layer is ``h = h + F(N(h))`` with one ``F``
(``hybrid_override_pattern``: ``M`` a scan, ``E`` a mixture, ``*`` an
attention); ``logits = N(h_L) @ lm_head``, untied. ``N`` is an RMSNorm with
a plain weight drawn as ones, eps 1e-5.

- ``M`` is ``ops/ssm.mamba2_mixer`` at ``ssm_heads`` heads in ``ssm_groups``
  groups of B and C (head ``i`` reads group ``i // (heads / groups)``), a
  chunk of ``ssm_chunk``, and a gated RMSNorm that norms each group's
  channels on its own (``mamba2_part(norm_groups=)``).
- ``*`` is ``llama.attention_block`` with no position embedding (q and k are
  not rotated), scores scaled by the head size.
- ``E`` is ``ops/moe.routed_part`` in a latent: ``s = sigmoid(u W_r)``
  float32 over all ``num_experts``, the choice the ``top_k`` largest of ``s
  + b`` (``b`` the router's selection bias, no optimizer's, moved by
  ``update_router_bias`` after a step), the weights ``routed_scale * s /
  (sum s + renorm_eps)``; the experts multiply ``u W_dn`` (``moe_latent_size``
  columns), each ``relu(l W1)^2 W2``, and the tokens' sums go through
  ``W_up`` once; beside them a squared-ReLU MLP of
  ``shared_intermediate_size`` on ``u`` itself.
- The prediction module (``mtp_layer_pattern``, ``*E``: depth 1 of
  DeepSeek-V3's section 2.2): ``models/stack.Stack(mtp=)``. ``loss_terms``
  takes ``seq + 2`` ids a row and adds ``mtp_loss_scale`` times the module's
  cross entropy through the model's own embedding and head.

``experts_held=(first, count)`` is this chip's share of each routed layer
under expert parallelism (``models/laguna.py``'s docstring); the module's
mixture holds the same indices. No auxiliary balancing loss. The model is
the table ``LAYER_KINDS`` of one-part kinds and ``models/stack.py`` walks
it; ``forward`` and ``token_nll`` are the main model's and hand back
``{"ssm_state": .., "router": ..}``. Training only: the serving engines keep
no scan state and draft no token.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Tuple

import jax.numpy as jnp

from ray_tpu.models import llama, stack
from ray_tpu.ops.moe import routed_part
from ray_tpu.ops.ssm import mamba2_part

# the published hybrid_override_pattern: 88 layers, 40 M, 40 E, 8 *
PATTERN_SUPER = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                 "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
_KIND = {"M": "mamba", "E": "moe", "*": "attention"}


@dataclass(frozen=True)
class Nemotron_hConfig(llama.LlamaConfig):
    # one letter a layer: M a scan, E a mixture, * an attention
    layer_pattern: str = "ME*"
    # the prediction module's layers, in the same letters; "" for none
    mtp_layer_pattern: str = "*E"
    mtp_loss_scale: float = 0.1             # Megatron-Core's default
    ssm_heads: int = 128                    # mamba_num_heads
    ssm_head_dim: int = 64                  # mamba_head_dim
    ssm_state: int = 128                    # ssm_state_size
    ssm_groups: int = 8                     # n_groups
    ssm_conv_taps: int = 4                  # conv_kernel
    ssm_chunk: int = 128                    # chunk_size
    num_experts: int = 512                  # the router's outputs
    experts_held: Optional[Tuple[int, int]] = None
    # a pass of the held rows is their balanced share and one part in this
    # many of it (``ops/moe._held_chunk``; 0.5: twice the share over it);
    # None: the op's own part
    held_headroom: Optional[float] = None
    top_k: int = 22
    routed_scale: float = 5.0               # routed_scaling_factor
    renorm_eps: float = 1e-20
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688
    shared_intermediate_size: int = 5376
    bias_update_rate: float = 0.001
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = False

    def __post_init__(self):
        super().__post_init__()
        for name in ("layer_pattern", "mtp_layer_pattern"):
            unknown = set(getattr(self, name)) - set(_KIND)
            if unknown:
                raise ValueError(f"{name} holds {sorted(unknown)}: a layer "
                                 "is M, E or *")
        if len(self.layer_pattern) != self.num_layers:
            raise ValueError(
                f"layer_pattern names {len(self.layer_pattern)} layers, "
                f"num_layers is {self.num_layers}")
        if self.ssm_heads % self.ssm_groups:
            raise ValueError("ssm_groups does not divide ssm_heads")

    @property
    def pattern(self) -> Tuple[str, ...]:
        """The kind of each layer, in order."""
        return tuple(_KIND[c] for c in self.layer_pattern)

    @property
    def mtp_pattern(self) -> Tuple[str, ...]:
        """The kind of each of the prediction module's layers."""
        return tuple(_KIND[c] for c in self.mtp_layer_pattern)

    @classmethod
    def nemotron_3_super_120b_a12b(cls, **kw) -> "Nemotron_hConfig":
        """The published config.json: 88 layers of one part, 512 experts of
        2,688 in a latent of 1,024, one prediction module. ``layer_pattern``
        names a cut of the stack."""
        pattern = kw.get("layer_pattern", PATTERN_SUPER)
        sizes = dict(vocab_size=131_072, hidden_size=4096,
                     intermediate_size=2688, num_layers=len(pattern),
                     num_heads=32, num_kv_heads=2, head_dim=128,
                     max_seq_len=262_144, rope_theta=10_000.0,
                     layer_pattern=pattern)
        return cls(**{**sizes, **kw})

    @classmethod
    def tiny(cls, **kw) -> "Nemotron_hConfig":
        cfg = cls(vocab_size=256, hidden_size=64, intermediate_size=32,
                  num_layers=5, num_heads=4, num_kv_heads=2, head_dim=16,
                  max_seq_len=64, layer_pattern="MEM*E", ssm_heads=8,
                  ssm_head_dim=16, ssm_state=16, ssm_groups=2, ssm_chunk=8,
                  num_experts=16, top_k=4, moe_latent_size=32,
                  moe_intermediate_size=48, shared_intermediate_size=96,
                  dtype=jnp.float32, remat=False)
        return replace(cfg, **kw)


LAYER_KINDS = {
    "mamba": (mamba2_part(norm_groups="ssm_groups"),),
    "attention": (llama.attention_part(rope=None),),
    "moe": (routed_part(shared="relu2", score="sigmoid", bias=True,
                        renorm_eps="renorm_eps", latent="moe_latent_size",
                        act="relu2"),)}
STACK = stack.Stack(LAYER_KINDS, reports=("ssm_state", "router"),
                    blocked_head=True, mtp="mtp_pattern")

logical_axes = STACK.logical_axes
init_params = STACK.init_params
param_shardings = STACK.param_shardings
forward = STACK.forward
token_nll = STACK.token_nll
token_nlls = STACK.token_nlls
loss_terms = STACK.loss_terms
loss_fn = STACK.loss_fn
rows_held, rows_passed = stack.rows_held, stack.rows_passed
# the bias's: what an optimizer is given and gives back, the move after a
# step, the counter (``models/stack.py``)
trainable, with_trainable = stack.trainable, stack.with_trainable
update_router_bias = STACK.update_router_bias
router_bias_abs_max = stack.router_bias_abs_max
