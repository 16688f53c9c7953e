"""Llama-3-style decoder-only transformer, TPU-first.

Design (none of this exists in the reference — it delegates models to
torch; this is the flagship model the north-star configs name):

- plain-jax pytree params with *stacked* layers, walked by ``run_layers``
  (the one loop of the family: llama, mixtral, olmoe and the pipeline
  stage): a ``lax.scan`` over the stack, one layer traced/compiled once
  regardless of depth, or unrolled where ``scan_layers=False``.
- every parameter carries logical axis names (parallel/sharding.py) so the
  same model runs dp/fsdp/tp/sp by choosing a mesh; no model code changes.
- bf16 params/activations with fp32 accumulations (preferred_element_type)
  — MXU-native.
- rematerialization: ``run_layers`` puts one ``jax.checkpoint`` around
  each layer, keeping what its level names (``REMAT_LADDER``). The level
  comes from bytes (``remat_plan``: the richest rungs that fit the
  device's memory, by kind of layer in a stack of kinds); a forward
  without a plan (Mixtral's, the pipeline stage) runs "full".
- attention backend switch: "flash" (Pallas), "reference" (XLA), "ring"
  (sequence-parallel over the sp axis, KV blocks rotating on the ICI
  ring), "ulysses" (sequence-parallel via all-to-all head re-sharding).
"""

from __future__ import annotations

import itertools
import math
from dataclasses import dataclass, field, replace
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops import dsa
from ray_tpu.ops.attention import attention_reference, flash_attention
from ray_tpu.ops.layers import (Leaf, Part, apply_rope, blocked_head_loss,
                                blocked_head_nll, embed_rows, head_block,
                                kept, layer_norm, norm_start, rms_norm,
                                rope_frequencies, swiglu, swiglu_part)
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.util import tracing

# What a layer's jax.checkpoint keeps besides the layer's input, rung by
# rung in order of step time saved per byte kept (PERF.md 6, PR 27):
# checkpoint names given where the values are born (attention_block,
# ops/attention._flash_fwd, ops/layers.swiglu, ops/moe._swiglu_rows,
# ops/ssm.mamba2_mixer).
# "level<n>" keeps the names of the first n rungs. The norms and
# act(gate) * up are recomputed at every level: elementwise and cheap, and
# as large again as all four rungs.
REMAT_LADDER = (
    # the backward's second flash_fwd; of a latent-attention layer
    # (ops/mla.py) its two latents besides, a quarter of the rung's bytes
    # there: the backward then reruns the expansions from them and
    # neither down-projection; of an index layer (ops/dsa.py) its packed
    # choice besides, and its backward runs no block's forward again; of
    # a scan layer (ops/ssm.py) its in-projection's output, the widest
    # product of the layer, which the backward then runs three times and
    # not four: by the ladder's order the scan kind's first, 7.1 ms for
    # the 304 MB it is at Nemotron's widths and 8,192 tokens, where the
    # taps' and the scan's second forwards beside it are ~2.7 ms for as
    # many bytes and stay recomputed, reading the kept array (PERF.md 6,
    # PR 59)
    ("flash_out", "flash_lse", "q_latent", "kv_latent", "dsa_choice",
     "ssm_in"),
    ("q_rope", "k_rope", "v_proj"),     # the q/k/v matmuls and rope
    ("mlp_gate", "mlp_up"),             # the gate and up matmuls, grouped too
    ("attn_resid",),                    # the wo matmul
)
REMAT_POLICIES = ("auto", "full") + tuple(
    f"level{n}" for n in range(1, len(REMAT_LADDER) + 1))
# remat_plan's two constants, calibrated against the TPU compiler
# (PERF.md 6, PR 27: 33 step programs compiled for a v5e, two to sixteen
# layers, 4k to 16k tokens a device, with and without fsdp). XLA's heap
# for a step under the layer scan comes out about half again what is live
# in it at its fullest (the compiler's own report: 35-47% fragmentation);
# the stacks a level keeps cost their own bytes on top. Reckoned so, no
# program read more than 3% over its estimate. The reserve covers that
# and what lives beside the program (the next batch, the step's outputs).
REMAT_HEAP_FACTOR = 1.5
REMAT_RESERVE = 0.05


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    hidden_size: int = 4096
    intermediate_size: int = 14_336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: Optional[int] = None
    max_seq_len: int = 8192
    rope_theta: float = 500_000.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attn_impl: str = "auto"  # auto | flash | reference | ring | ulysses
    # Qwen2-style additive q/k/v projection biases (the ONLY
    # architectural delta between Qwen2 and Llama at this level)
    attn_qkv_bias: bool = False
    # Gemma deltas: GeGLU gate activation ("gelu_tanh"), and embeddings
    # scaled by sqrt(hidden) at lookup. Gemma's (1+w) RMSNorm needs no
    # knob — the +1 folds into the stored norm weights at load time.
    mlp_act: str = "silu"  # silu | gelu_tanh
    # every RMSNorm of the block, a head's q/k norms and the last norm
    # scale by 1 + w, w drawn as zeros (Qwen3-Next; ops/layers.rms_norm)
    zero_centred_norm: bool = False
    embed_scale: float = 1.0
    # serving prefill attention: None = auto (Pallas flash on single-
    # chip TPU, fp32 reference elsewhere). The engine forces False under
    # tensor parallelism — a pallas_call inside a GSPMD-sharded jit
    # cannot be auto-partitioned like plain XLA ops.
    prefill_flash: Optional[bool] = None
    remat: bool = True
    # What a layer's jax.checkpoint keeps for its backward. "full": the
    # layer's input alone, the whole forward runs again. "level1" ..
    # "level4": the names of REMAT_LADDER's first n rungs besides.
    # "auto" (the default): the richest of those that remat_plan reckons
    # to fit the device's memory, one for each kind of layer of a stack of
    # kinds (OLMoE, Laguna and LFM2 share the plan), "full" where the
    # device reports none (the CPU) and in a forward that has no plan
    # (Mixtral, the pipeline schedule: remat_level_without_plan).
    remat_policy: str = "auto"
    # False = python-unrolled layer loop instead of lax.scan, in every
    # forward of the family (run_layers honours it). The scan
    # carries the stacked weight GRADIENTS through its backward as
    # dynamic-update-slice'd buffers, which XLA partially re-copies per
    # iteration; unrolling removes that and measured +3% step throughput
    # at 1B on the old machine (855→806 ms; round 5; not measured on this
    # repo's v5e: ROADMAP S8).
    # Cost: compile time grows with depth (~30 s at 16 layers) — the
    # right trade for long training runs, wrong for tests/CI, so scan
    # stays the default.
    scan_layers: bool = True
    tie_embeddings: bool = False
    # optional llama3-style long-context rope scaling (the HF
    # rope_scaling dict; see ops/layers.rope_frequencies)
    rope_scaling: Optional[tuple] = None  # dict items, hashable for jit

    def __post_init__(self):
        # validate eagerly (not just when remat kicks in) so a typo'd
        # policy on a remat=False config cannot sit unnoticed until a
        # later remat=True run crashes at trace time
        if self.remat_policy not in REMAT_POLICIES:
            raise ValueError(
                f"unknown remat_policy {self.remat_policy!r} "
                f"({' | '.join(REMAT_POLICIES)})")

    @property
    def rope_scaling_dict(self):
        return dict(self.rope_scaling) if self.rope_scaling else None

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    # ---- presets -----------------------------------------------------------

    @classmethod
    def llama3_8b(cls, **kw) -> "LlamaConfig":
        return cls(**kw)

    @classmethod
    def llama3_1b_proxy(cls, **kw) -> "LlamaConfig":
        cfg = cls(hidden_size=2048, intermediate_size=5504, num_layers=16,
                  num_heads=16, num_kv_heads=8, vocab_size=32_000)
        return replace(cfg, **kw)

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        cfg = cls(vocab_size=256, hidden_size=64, intermediate_size=128,
                  num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128,
                  dtype=jnp.float32, remat=False)
        return replace(cfg, **kw)


# Logical axis names for every parameter (rules in parallel/sharding.py map
# them onto the mesh; the leading "layer" dim of stacked params is unsharded
# until pipeline parallelism assigns it to "pp").
def logical_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    L = ("layer",)
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": L + ("embed",),
            "wq": L + ("embed", "qkv"),
            "wk": L + ("embed", "qkv"),
            "wv": L + ("embed", "qkv"),
            "wo": L + ("qkv", "embed"),
            "mlp_norm": L + ("embed",),
            "w_gate": L + ("embed", "mlp"),
            "w_up": L + ("embed", "mlp"),
            "w_down": L + ("mlp", "embed"),
            # qkv biases shard with their projections' column split
            **({"bq": L + ("qkv",), "bk": L + ("qkv",),
                "bv": L + ("qkv",)} if cfg.attn_qkv_bias else {}),
        },
        "final_norm": ("embed",),
        # tied embeddings reuse params["embed"]; no separate lm_head leaf
        **({} if cfg.tie_embeddings else {"lm_head": ("embed", "vocab")}),
    }


def init_params(cfg: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    """Truncated-normal init (fan-in scaled), params in cfg.param_dtype."""
    h, ffn, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    hd = cfg.head_dim_
    qd = cfg.num_heads * hd
    kvd = cfg.num_kv_heads * hd
    keys = jax.random.split(key, 8)

    def norm_init(k, shape, fan_in):
        return (jax.random.truncated_normal(k, -3, 3, shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(cfg.param_dtype)

    params = {
        "embed": norm_init(keys[0], (cfg.vocab_size, h), h),
        "layers": {
            "attn_norm": jnp.ones((L, h), cfg.param_dtype),
            "wq": norm_init(keys[1], (L, h, qd), h),
            "wk": norm_init(keys[2], (L, h, kvd), h),
            "wv": norm_init(keys[3], (L, h, kvd), h),
            "wo": norm_init(keys[4], (L, qd, h), qd),
            "mlp_norm": jnp.ones((L, h), cfg.param_dtype),
            "w_gate": norm_init(keys[5], (L, h, ffn), h),
            "w_up": norm_init(keys[6], (L, h, ffn), h),
            "w_down": norm_init(keys[7], (L, ffn, h), ffn),
        },
        "final_norm": jnp.ones((h,), cfg.param_dtype),
    }
    if cfg.attn_qkv_bias:
        params["layers"]["bq"] = jnp.zeros((L, qd), cfg.param_dtype)
        params["layers"]["bk"] = jnp.zeros((L, kvd), cfg.param_dtype)
        params["layers"]["bv"] = jnp.zeros((L, kvd), cfg.param_dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = norm_init(
            jax.random.fold_in(key, 99), (h, cfg.vocab_size), h)
    return params


def remat_names(policy: str) -> Tuple[str, ...]:
    """The checkpoint names a resolved ``remat_policy`` keeps."""
    level = 0 if policy == "full" else int(policy[len("level"):])
    return tuple(n for rung in REMAT_LADDER[:level] for n in rung)


def _runs(pattern: Tuple[str, ...]) -> Tuple[Tuple[str, int], ...]:
    """A stack's runs in its order: (kind, layers of it in a row)."""
    return tuple((kind, len(list(run)))
                 for kind, run in itertools.groupby(pattern))


def describe_stack(cfg: LlamaConfig, kinds: Dict[str, Tuple[Part, Part]],
                   layers, tokens_per_device: int,
                   pattern: Optional[Tuple[str, ...]] = None,
                   head_tokens: Optional[int] = None, mesh=None
                   ) -> Dict[str, Any]:
    """What ``remat_plan`` knows of a stack: its ``runs`` (``_runs``; one
    run of "layer" without a ``pattern``) and for each of its ``kinds`` the
    bytes each rung of REMAT_LADDER keeps in one layer, the bytes a layer
    holds while its backward runs, and the parameters of its matrices.
    ``kinds``: the model's table ``kind -> (mixer, mlp)``; each part says
    what a layer of it keeps (``Part.keeps``, beside the code that runs
    it) from the shapes of the kind's stacked parameters
    ``layers[kind][name]``, as the layer's own code reads them, the tokens
    and the config, and a kind's two are summed here. ``mesh``: the one
    the arrays are sharded over, if any (which form a scan or a rule runs
    follows from it). A kind the table has no entry for, a leaf neither
    of its parts names or a layer without a matrix they name raises: a
    layer the plan does not know is not reckoned as another.
    ``head_tokens``: the tokens whose logits exist at a time where the
    head and loss walk blocks (``blocked_token_nll``); all, without."""
    T, h = tokens_per_device, cfg.hidden_size
    act = jnp.dtype(cfg.dtype).itemsize
    depth = jax.tree_util.tree_leaves(layers)[0].shape[0]
    runs = _runs(pattern or ("layer",) * depth)
    described = {}
    for kind in dict(runs):
        leaves = layers[kind] if pattern else layers
        shape = {name: a.shape[1:] for name, a in leaves.items()}
        if kind not in kinds:
            raise ValueError(
                f"describe_stack does not know the layer kind {kind!r}: "
                f"the table has {sorted(kinds)}")
        parts = kinds[kind]
        named = {n: leaf for part in parts
                 for n, leaf in part.leaves(cfg).items()}
        unknown = sorted(set(shape) - set(named))
        lacking = [n for n, leaf in named.items()
                   if len(leaf.shape) > 1 and n not in shape]
        if unknown or lacking:
            raise ValueError(
                f"describe_stack does not know the layer kind {kind!r}: "
                + (f"neither of its parts names the leaves {unknown}"
                   if unknown else f"it lacks {lacking} of its parts"))
        keeps = [part.keeps(cfg, shape, T, mesh) for part in parts]
        # elements a token that a layer's backward holds: its recomputed
        # forward (norms, projections, the mixer, the MLP's arrays) and
        # the gradients of the widest of them
        width = 4 * h + sum(k["width"] for k in keeps)
        described[kind] = {
            "rungs": tuple(map(sum, zip(*(k["rungs"] for k in keeps)))),
            "working_bytes": T * act * width + sum(k["rows"] for k in keeps),
            "params": sum(math.prod(s) for s in shape.values()
                          if len(s) > 1)}
    return {"runs": runs, "kinds": described,
            **({"head_tokens": head_tokens} if head_tokens else {})}


def remat_plan(cfg: LlamaConfig, stack: Dict[str, Any],
               tokens_per_device: int, param_bytes_per_device: int,
               capacity_bytes: Optional[int], params_sharded: bool
               ) -> Dict[str, Any]:
    """Which rungs of REMAT_LADDER each kind of layer of a train step
    keeps (``stack``: ``describe_stack``): a pure function of shapes and
    bytes, so the same inputs always give the same program.
    ``remat_policy="auto"`` climbs the ladder rung by rung, its order of
    time saved per byte: of the kinds that took every rung before, those
    take a rung that together keep the most with the need reckoned within
    ``capacity_bytes * (1 - REMAT_RESERVE)``, and a kind that does not
    stops there. A kind's level is the last rung it took that keeps
    anything in it, "full" where none does or there is no capacity to
    read; any other policy is every kind's level as set, with the need
    reckoned beside it. ``level``, ``saved_bytes_per_layer`` and
    ``layers`` are dicts by kind, and the one kind's own for a stack of
    one ("layer").

    The need, per device: parameters and two moments of their dtype,
    resident, and the step's heap at its fullest moment. The backward
    walks the runs from the last to the first. While a run's layers are
    in it the heap holds, times REMAT_HEAP_FACTOR: under a
    parameter-sharding mesh the gathered weights in flight (two layers,
    embedding and head, and their gradients before the reduction), the
    input of every layer up to the run's last, the gradients of the run
    and of all above it, and one layer's internals; before the first, in
    place of the last two, the float32 logits with their gradient. On top
    comes what the levels keep in the layers up to the run's last (what
    the layers above kept is freed by then): its own bytes, and times the
    factor where a scanned run of a stack of several keeps it (read
    back from the stack slice by slice: PERF.md 6, PR 33). In a stack of
    one run that is PR 27's reckoning, unchanged."""
    T, h = tokens_per_device, cfg.hidden_size
    act = jnp.dtype(cfg.dtype).itemsize
    par = jnp.dtype(cfg.param_dtype).itemsize
    runs, kinds = stack["runs"], stack["kinds"]
    depth = {k: sum(n for kind, n in runs if kind == k) for k in kinds}
    gathered = 0
    if params_sharded:
        top = (1 if cfg.tie_embeddings else 2) * cfg.vocab_size * h
        gathered = 2 * (2 * max(k["params"] for k in kinds.values())
                        + top) * par
    logits = 2 * stack.get("head_tokens", T) * cfg.vocab_size * 4

    def saved(kind: str, level: str) -> int:
        return sum(b for rung, b in zip(REMAT_LADDER, kinds[kind]["rungs"])
                   if set(rung) <= set(remat_names(level)))

    def need(level: Dict[str, str]) -> int:
        below = grads_below = stacked = freed = fullest = 0
        for kind, n in runs:
            below += n
            kept = n * saved(kind, level[kind])
            if len(runs) > 1 and cfg.scan_layers and n > 1:
                stacked += kept
            else:
                freed += kept
            live = (gathered + below * T * h * act
                    + kinds[kind]["working_bytes"]
                    + param_bytes_per_device - grads_below)
            fullest = max(fullest,
                          REMAT_HEAP_FACTOR * (live + stacked) + freed)
            grads_below += n * kinds[kind]["params"] * par
        live = gathered + below * T * h * act + logits
        fullest = max(fullest, REMAT_HEAP_FACTOR * (live + stacked) + freed)
        return 3 * param_bytes_per_device + int(fullest)

    def labels(taken: Dict[str, int]) -> Dict[str, str]:
        # a rung that keeps nothing in a kind does not name its level: a
        # convolution layer is "full" whatever its neighbours keep
        last = {k: max((n + 1 for n in range(taken[k])
                        if kinds[k]["rungs"][n]), default=0) for k in kinds}
        return {k: f"level{n}" if n else "full" for k, n in last.items()}

    level = dict.fromkeys(kinds, cfg.remat_policy)
    if cfg.remat_policy == "auto":
        taken = dict.fromkeys(kinds, 0)       # rungs each kind keeps
        budget = (capacity_bytes or 0) * (1 - REMAT_RESERVE)
        climbing = tuple(kinds)
        for rung in range(len(REMAT_LADDER)):
            # most kinds first and in the stack's order: ties fall one way
            climbing = max(
                (c for n in range(len(climbing), 0, -1)
                 for c in itertools.combinations(climbing, n)
                 if need(labels({**taken, **dict.fromkeys(c, rung + 1)}))
                 <= budget),
                key=lambda c: sum(depth[k] * kinds[k]["rungs"][rung]
                                  for k in c), default=())
            taken.update(dict.fromkeys(climbing, rung + 1))
        level = labels(taken)
    by_kind = {"level": level, "layers": depth,
               "saved_bytes_per_layer": {k: saved(k, level[k])
                                         for k in kinds}}
    if tuple(kinds) == ("layer",):
        by_kind = {name: of["layer"] for name, of in by_kind.items()}
    return {**by_kind, "need_bytes": need(level),
            "capacity_bytes": capacity_bytes}


def _device_capacity(mesh) -> Optional[int]:
    """The memory limit of one of this process's devices the program will
    run on, where the backend reports one: the CPU reports none, and a
    device that is described and not attached (a compile ahead of time)
    refuses the question."""
    dev = jax.devices()[0] if mesh is None else mesh.local_devices[0]
    try:
        return (dev.memory_stats() or {}).get("bytes_limit")
    except jax.errors.JaxRuntimeError:
        return None


def resolve_remat(cfg: LlamaConfig, kinds, params, tokens, mesh,
                  shardings=None, **stack) -> Any:
    """The remat level of the program being traced (by kind for a stack
    with a ``pattern``), from its shapes: no device work and no trial
    compile. ``kinds``: the forward's table of parts; ``shardings``: its
    own ``param_shardings``; ``stack``: ``describe_stack``'s ``pattern``
    and ``head_tokens``. The plan is one kept span, so an operator reads
    in ``trace_spans.json`` and ``timeline()`` which level a job got and
    why."""
    total = sum(a.size * a.dtype.itemsize
                for a in jax.tree_util.tree_leaves(params))
    per_device, data_shards = total, 1
    if mesh is not None:
        from ray_tpu.parallel.sharding import resolve_axis

        per_device = sum(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            lambda a, sh: math.prod(sh.shard_shape(a.shape))
            * a.dtype.itemsize, params,
            (shardings or param_shardings)(cfg, mesh))))
        sizes = dict(mesh.shape)
        for logical in ("batch", "seq"):
            axes = resolve_axis(logical, mesh) or ()
            for axis in (axes,) if isinstance(axes, str) else axes:
                data_shards *= sizes[axis]
    per_shard = -(-tokens.size // data_shards)
    plan = remat_plan(cfg, describe_stack(cfg, kinds, params["layers"],
                                          per_shard, mesh=mesh, **stack),
                      per_shard, per_device, _device_capacity(mesh),
                      per_device < total)
    with tracing.span("rtpu.train.remat_plan", keep=True, **plan):
        pass
    return plan["level"]


def _attend(cfg: LlamaConfig, q, k, v, mesh=None, seq_axis=None,
            window=None, sm_scale=None):
    impl = cfg.attn_impl
    if impl == "auto":
        impl = "flash" if jax.default_backend() == "tpu" else "reference"
    if window is not None and impl in ("ring", "ulysses"):
        raise ValueError(f"attn_impl={impl!r} knows no window: a window "
                         "layer runs \"flash\" or \"reference\"")
    if sm_scale is not None and impl in ("ring", "ulysses"):
        raise ValueError(f"attn_impl={impl!r} scales its scores by the "
                         "head size alone: a layer with a stated scale "
                         "runs \"flash\" or \"reference\"")
    if impl == "flash":
        if mesh is None:
            return flash_attention(q, k, v, causal=True, window=window,
                                   sm_scale=sm_scale)
        # A pallas_call is opaque to GSPMD: left bare under a sharded jit,
        # XLA gathers the whole batch onto every chip and runs the kernel
        # on all of it. shard_map hands each chip its own batch rows (and
        # its heads, when tp divides the KV heads); the sequence stays
        # whole — splitting it is ring/ulysses' job.
        from jax.sharding import PartitionSpec as P

        from ray_tpu.parallel.sharding import resolve_axis

        tp = dict(mesh.shape).get("tp", 1)
        heads = "tp" if tp > 1 and cfg.num_kv_heads % tp == 0 else None
        spec = P(resolve_axis("batch", mesh), None, heads, None)
        return jax.shard_map(
            lambda q_, k_, v_: flash_attention(q_, k_, v_, causal=True,
                                               window=window,
                                               sm_scale=sm_scale),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(q, k, v)
    if impl in ("ring", "ulysses"):
        if seq_axis is not None:
            # already INSIDE a shard_map that includes the sp axis (the
            # pp pipeline program): run the per-shard body directly
            if impl == "ring":
                from ray_tpu.ops.ring_attention import ring_attention_local

                return ring_attention_local(q, k, v, seq_axis, causal=True)
            from ray_tpu.ops.ulysses import ulysses_attention_local

            return ulysses_attention_local(q, k, v, seq_axis, causal=True)
        if mesh is None:
            raise ValueError(
                f"attn_impl={impl!r} requires a mesh with an 'sp' axis")
        if impl == "ring":
            return ring_attention(q, k, v, mesh, axis_name="sp", causal=True)
        from ray_tpu.ops.ulysses import ulysses_attention

        return ulysses_attention(q, k, v, mesh, axis_name="sp", causal=True)
    return attention_reference(q, k, v, causal=True, window=window,
                               sm_scale=sm_scale)


def _wide_gated(attn, gate):
    """attn and gate [b, s, heads, head_dim] -> ``attn * sigmoid(gate)``
    dim by dim, float32 inside."""
    return (attn.astype(jnp.float32) * jax.nn.sigmoid(
        gate.astype(jnp.float32))).astype(attn.dtype)


def attention_block(cfg: LlamaConfig, x, p, cos, sin, mesh=None,
                    seq_axis=None, window=None, sm_scale=None,
                    resid_scale=None, gate_in_wq: bool = False,
                    attend=None):
    """Attention sub-block with residual: x + wo(attend(qkv)), the norm
    where the layer's leaves put it: ``attn_norm`` on the block's input
    (pre-norm, llama's order), ``attn_post_norm`` on its output before the
    sum (OLMo 2's order; a layer has one of the two).
    Shared by every model in the family (llama dense, mixtral, olmoe and
    laguna MoE, granite, olmo_hybrid). The number of query heads is the
    layer's own, read from
    its ``wq`` (Laguna's window layers have more than its full ones);
    ``window``: the layer sees that many keys back (``flash_attention``);
    a ``wg`` in ``p`` is a per-head output gate, ``sigmoid(norm(x) @ wg)``
    on each head's output before ``wo`` (arXiv:2505.06708, headwise);
    ``q_norm`` and ``k_norm`` are an RMSNorm of q and k before rope, over
    the whole vector or, with a weight of a head's size, over each head.
    ``cos`` and ``sin`` of None: the layer has no position embedding and
    q and k are not rotated; ``sm_scale``: the scores' scale where it is
    not ``head_dim ** -0.5``; ``resid_scale``: the weight of the block's
    output in the sum with ``x`` where it is not 1 (Granite's
    ``attention_multiplier`` and ``residual_multiplier``). Left at None
    the three trace what they always did. ``gate_in_wq``: ``wq`` is twice
    as wide and gives each head its query and then, of the same size, an
    elementwise gate: ``sigmoid`` of it multiplies that head's output dim
    by dim before ``wo`` (Qwen3-Next). ``cfg.zero_centred_norm``: every
    norm here scales by ``1 + w``. ``attend(u, q, k, v) -> attn``: what
    stands in for the causal attention under its own scopes, given the
    block's normed input and its rotated heads (``attention_part(index=
    True)``: attention over the keys a learned index chooses)."""
    # The named scopes here and below (embed, attn_qkv, flash, attn_out,
    # mlp, head_loss) are metadata only: they name the device time of a
    # step in a profiler trace and change no instruction.
    b, s, _ = x.shape
    hd = cfg.head_dim_
    norm = partial(rms_norm, eps=cfg.rms_norm_eps,
                   zero_centred=cfg.zero_centred_norm)
    with jax.named_scope("attn_qkv"):
        h1 = norm(x, p["attn_norm"]) if "attn_norm" in p else x
        q = jnp.dot(h1, p["wq"].astype(cfg.dtype),
                    preferred_element_type=jnp.float32).astype(cfg.dtype)
        k = jnp.dot(h1, p["wk"].astype(cfg.dtype),
                    preferred_element_type=jnp.float32).astype(cfg.dtype)
        v = jnp.dot(h1, p["wv"].astype(cfg.dtype),
                    preferred_element_type=jnp.float32).astype(cfg.dtype)
        if "bq" in p:  # Qwen2-style qkv biases (structure is trace-static)
            q = q + p["bq"].astype(cfg.dtype)
            k = k + p["bk"].astype(cfg.dtype)
            v = v + p["bv"].astype(cfg.dtype)
        # a q/k norm's weight says what it is over: [hd] each head's dims
        # (LFM2), else the whole q and k vectors (OLMoE)
        per_head = "q_norm" in p and p["q_norm"].shape[-1] == hd
        if "q_norm" in p and not per_head:
            q = norm(q, p["q_norm"])
            k = norm(k, p["k_norm"])
        heads = p["wq"].shape[-1] // (2 * hd if gate_in_wq else hd)
        q = q.reshape(b, s, heads, -1)
        if gate_in_wq:
            q, wide_gate = q[..., :hd], q[..., hd:]
        k = k.reshape(b, s, cfg.num_kv_heads, hd)
        v = v.reshape(b, s, cfg.num_kv_heads, hd)
        if per_head:
            q = norm(q, p["q_norm"])
            k = norm(k, p["k_norm"])
        if cos is not None:
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        # named for a remat level that keeps them (REMAT_LADDER; no-ops
        # otherwise): the backward then skips the q/k/v matmuls and rope
        q = checkpoint_name(q, "q_rope")
        k = checkpoint_name(k, "k_rope")
        v = checkpoint_name(v, "v_proj")
        if "wg" in p:
            with jax.named_scope("attn_gate"):
                gate = jax.nn.sigmoid(jnp.dot(
                    h1, p["wg"].astype(cfg.dtype),
                    preferred_element_type=jnp.float32))
    # a window layer's kernel calls are ``flash_win`` inside ``flash``: a
    # reader that knows ``flash`` alone still finds them there
    def causal():
        with jax.named_scope("flash"):
            if window is None:
                return _attend(cfg, q, k, v, mesh=mesh, seq_axis=seq_axis,
                               sm_scale=sm_scale)
            with jax.named_scope("flash_win"):
                return _attend(cfg, q, k, v, mesh=mesh, seq_axis=seq_axis,
                               window=window, sm_scale=sm_scale)

    attn = causal() if attend is None else attend(h1, q, k, v)
    with jax.named_scope("attn_out"):
        if "wg" in p:
            with jax.named_scope("attn_gate"):
                attn = (attn.astype(jnp.float32) * gate[..., None]
                        ).astype(cfg.dtype)
        if gate_in_wq:
            with jax.named_scope("attn_gate"):
                # looked up at trace time: delta_moe_limits.py's seam
                attn = _wide_gated(attn, wide_gate)
        attn = attn.reshape(b, s, heads * hd)
        attn_out = jnp.dot(
            attn, p["wo"].astype(cfg.dtype),
            preferred_element_type=jnp.float32).astype(cfg.dtype)
        if "attn_post_norm" in p:
            attn_out = norm(attn_out, p["attn_post_norm"])
        if resid_scale is not None:
            attn_out = attn_out * jnp.asarray(resid_scale, cfg.dtype)
        return checkpoint_name(x + attn_out, "attn_resid")


def rope_tables(cfg: LlamaConfig, tokens: jax.Array):
    """(cos, sin) over the whole head at the config's ``rope_theta`` and
    ``rope_scaling``: what ``attention_part`` makes once a forward unless
    its table says otherwise."""
    return rope_frequencies(cfg.head_dim_, tokens.shape[1], cfg.rope_theta,
                            dtype=cfg.dtype, scaling=cfg.rope_scaling_dict)


def attention_part(heads: str = "num_heads", window: Optional[str] = None,
                   gate: bool = False, rope=rope_tables,
                   qk_norm: Optional[str] = None, norm: str = "pre",
                   scale: Optional[str] = None, resid: Optional[str] = None,
                   index: bool = False) -> Part:
    """``attention_block`` as a layer's mixer. ``heads``, ``window``, ``scale``
    (the scores') and ``resid`` (the weight of the block's output in the sum)
    name fields of the config; ``gate``: True, a per-head output gate ``wg``
    (Laguna), or "elementwise", a gate of a head's size beside each head's
    query in a ``wq`` twice as wide (Qwen3-Next); ``rope(cfg, tokens) -> (cos,
    sin)``, made once a forward, or None for a layer without a position
    embedding; ``qk_norm``: "whole" (an RMSNorm over the q and k vectors:
    OLMoE, OLMo 2) or "head" (over each head's dims: LFM2); ``norm``: "pre"
    (``attn_norm`` on the block's input) or "post" (``attn_post_norm`` on its
    output: OLMo 2). ``cfg.attn_qkv_bias`` adds Qwen2's three biases.
    ``index``: a learned index chooses ``cfg.index_topk`` keys a query and
    the layer attends over those alone, the grouped keys and values as they
    are (``ops/dsa.py``; ``ops/mla.py`` has the same option): ``q_i = u
    W_iq`` ``[index_heads, index_head_dim]`` from the layer's normed input
    (there is no query latent), ``k_i = LayerNorm(u W_ik)``, both rotated
    whole with tables of their own width, so ``rope`` then gives ``((cos,
    sin), (cos_i, sin_i))``, the heads' and the index's; ``u`` reaches the
    index under ``stop_gradient``; the layer reports under "dsa" and
    ``terms`` adds the index's loss as ``dsa.index_terms`` says. Pre-norm
    and no window only."""
    wide = gate == "elementwise"
    if index and (window or norm != "pre" or rope is None):
        raise ValueError("an index layer is pre-norm, rotated and sees every "
                         "key: no window, norm=\"pre\", a rope")

    def leaves(cfg):
        h, hd, n = cfg.hidden_size, cfg.head_dim_, getattr(cfg, heads)
        qd, kvd = n * hd, cfg.num_kv_heads * hd
        mat, vec = ("embed", "qkv"), ("qkv",)
        out, ones = {}, norm_start(cfg)
        if norm == "pre":
            out["attn_norm"] = Leaf((h,), ones, ("embed",))
        out.update(wq=Leaf((h, 2 * qd if wide else qd), h, mat),
                   wk=Leaf((h, kvd), h, mat), wv=Leaf((h, kvd), h, mat))
        if qk_norm == "whole":
            out.update(q_norm=Leaf((qd,), ones, vec),
                       k_norm=Leaf((kvd,), ones, vec))
        out["wo"] = Leaf((qd, h), qd, ("qkv", "embed"))
        if gate and not wide:
            out["wg"] = Leaf((h, n), h, ("embed", None))
        if qk_norm == "head":
            out.update(q_norm=Leaf((hd,), ones, (None,)),
                       k_norm=Leaf((hd,), ones, (None,)))
        if cfg.attn_qkv_bias:
            out.update(bq=Leaf((qd,), "zeros", vec),
                       bk=Leaf((kvd,), "zeros", vec),
                       bv=Leaf((kvd,), "zeros", vec))
        if norm == "post":
            out["attn_post_norm"] = Leaf((h,), ones, ("embed",))
        if index:
            out.update(dsa.index_leaves(cfg, h))
        return out

    def chosen_keys(cfg, p, ctx, tables, said):
        """``attention_block``'s ``attend`` of an index layer: the index's
        inputs from ``u``, the walk of ``ops/dsa.py`` over the grouped keys
        and values; what the layer reports goes into ``said``."""
        def attend(u, q, k, v):
            b, s = u.shape[:2]
            with jax.named_scope("dsa_proj"):
                def rotate(x):
                    if x.ndim == 3:           # the one key a position
                        return apply_rope(x[:, :, None], *tables)[:, :, 0]
                    return apply_rope(x, *tables)

                q_i, k_i, w_i = dsa.index_inputs(cfg, u, u, p, rotate,
                                                 layer_norm)
            keep = ctx.keep_index_choice
            attn, kl, pairs, *choice = dsa.sparse_attention(
                q, k, v, None, q_i, k_i, w_i,
                scale=(getattr(cfg, scale) if scale
                       else cfg.head_dim_ ** -0.5),
                topk=cfg.index_topk, block=cfg.index_block,
                tiers=cfg.index_tiers, mesh=ctx.mesh, keep_choice=keep,
                # kept on the ladder's first rung (the walk's output, its
                # log-sum-exp and its choice, ``dsa.KEPT_NAMES``), the
                # layer's backward runs no block's forward again: the walk
                # is most of the layer
                named=True)
            said.update(dsa.index_report(b, s, kl, pairs, {
                "choice": choice[0], "q_i": q_i, "k_i": k_i,
                "w": w_i} if keep else None))
            return attn

        return attend

    def body(cfg, x, p, ctx):
        tables = ctx.once[rope] if rope else (None, None)
        said, attend = {}, None
        if index:
            tables, of_index = tables
            attend = chosen_keys(cfg, p, ctx, of_index, said)
        return attention_block(
            cfg, x, p, *tables, mesh=ctx.mesh,
            window=getattr(cfg, window) if window else None,
            sm_scale=getattr(cfg, scale) if scale else None,
            resid_scale=getattr(cfg, resid) if resid else None,
            gate_in_wq=wide, attend=attend), said

    def keeps(cfg, shape, tokens, mesh):
        # ``wq``'s width gives the heads, as ``attention_block`` reads them
        qd, kvd = shape["wq"][-1] // (2 if wide else 1), shape["wk"][-1]
        act = jnp.dtype(cfg.dtype).itemsize
        if index:
            # what the walk keeps is the first rung's (its output, the
            # log-sum-exp and the choice: ``dsa.kept_bytes``); the index's
            # queries, key and head weights are recomputed at every level
            # and held by the layer's backward, beside one block of the
            # walk (``dsa.walk_rows``). The backward's forward forms q, k
            # and v again and not the walk's output, which is the kept one
            # (one ``qd`` a token where ``attention_block``'s count has
            # two: compiled at level4 for a v5e the peak rose 33 MB over
            # the checkpointed blocks' where the kept set is 138, PR 58)
            hd, J, di = cfg.head_dim_, shape["wi_w"][-1], shape["wi_k"][-1]
            widths = dsa.Widths(qd // hd, hd, 0, hd, J, di, cfg.dtype,
                                kvd // hd)
            blk, trs = dsa.walk_plan(tokens, cfg.index_block,
                                     cfg.index_tiers, widths)
            return kept(
                first=dsa.kept_bytes(tokens, blk, trs, widths, True),
                qkv=tokens * (qd + 2 * kvd) * act,
                resid=tokens * cfg.hidden_size * act,
                width=qd + 2 * kvd + 2 * (J * di + di + 2 * J),
                rows=dsa.walk_rows(cfg, tokens, widths))
        return kept(
            first=tokens * (qd * act + qd // cfg.head_dim_ * 4),  # lse: f32
            qkv=tokens * (qd + 2 * kvd) * act,
            resid=tokens * cfg.hidden_size * act,
            # (a wide gate: the projection's second half and its gradient)
            width=2 * qd + 2 * kvd + (2 * qd if wide else 0))

    return Part(leaves, body, keeps, once=rope,
                **({"reports": "dsa", "terms": dsa.index_terms}
                   if index else {}))


# the dense stack's one kind, as ``forward`` describes it to the plan
LAYER_KINDS = {"layer": (attention_part(), swiglu_part())}


def _layer(cfg: LlamaConfig, x, layer_params, cos, sin, mesh=None,
           seq_axis=None):
    """One decoder block. x: [b, s, h]."""
    p = layer_params
    x = attention_block(cfg, x, p, cos, sin, mesh=mesh,
                        seq_axis=seq_axis)
    with jax.named_scope("mlp"):
        h2 = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
        mlp = swiglu(h2, p["w_gate"].astype(cfg.dtype),
                     p["w_up"].astype(cfg.dtype),
                     p["w_down"].astype(cfg.dtype), act=cfg.mlp_act)
        return x + mlp


def remat_level_without_plan(cfg: LlamaConfig) -> Optional[str]:
    """The level ``run_layers`` gets from a forward that has no plan for
    its memory (Mixtral, the pipeline stage): "full" under ``cfg.remat``.
    A ladder level somebody set would be a silent no-op there, so it is
    refused."""
    if cfg.remat_policy not in ("auto", "full"):
        raise ValueError(
            f"remat_policy={cfg.remat_policy!r} is a level of the planned "
            "forwards' ladder; this forward has no plan and runs full remat "
            "(\"auto\" is \"full\" here) - drop it rather than read "
            "tuning signal from a no-op")
    return "full" if cfg.remat else None


def run_layers(layer_fn, x, layers, *, level: Optional[str], scan: bool,
               pattern: Optional[Tuple[str, ...]] = None):
    """The family's one loop over the stacked ``layers`` and its one
    ``jax.checkpoint``. ``layer_fn(x, p) -> (x, y)`` is one block (``y``
    may be None); returns the last ``x`` and the ``y``s stacked, as
    ``lax.scan`` does. ``level``: None (no remat), "full" or a level of
    REMAT_LADDER, or with a ``pattern`` a dict of those by kind.
    ``scan``: ``cfg.scan_layers``.

    ``pattern``: for a stack of unequal layers, the kind of each layer in
    order, e.g. ``("dense", "win", "win", "win", "full")``; ``layer_fn``
    and ``layers`` are then dicts by kind, a kind's layers stacked in
    their order, and the ``y``s come back so too. A run of layers of one
    kind is one ``lax.scan``; a layer between two of other kinds is
    walked alone. (A period of several kinds that repeats is not scanned
    as a period: no cell holds more than one.)"""
    one_kind = pattern is None
    if one_kind:
        depth = jax.tree_util.tree_leaves(layers)[0].shape[0]
        pattern, layer_fn, layers = (("layer",) * depth, {"layer": layer_fn},
                                     {"layer": layers})
    runs = _runs(pattern)
    if level is not None:
        if not isinstance(level, dict):
            level = dict.fromkeys(layer_fn, level)
        # Inside the scan the forward and the backward are two loops and
        # XLA cannot merge a recomputation back into the forward, so a
        # ladder level drops jax.checkpoint's barrier against that, as
        # jax advises under scan: at 7B widths it cost a gigabyte of
        # XLA's heap and 5% of the step (PERF.md 6, PR 27). "full" keeps
        # the program it always had, and so does a kind with a layer that
        # is walked alone: there the barrier is what keeps the remat.
        walked = {kind for kind, n in runs if not (scan and n > 1)}
        names = {kind: remat_names(level[kind]) for kind in layer_fn}
        layer_fn = {kind: jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(
                *names[kind]) if names[kind] else None,
            prevent_cse=kind in walked or not names[kind])
            for kind, fn in layer_fn.items()}
    tree_map = jax.tree_util.tree_map
    taken = dict.fromkeys(layers, 0)      # layers of each kind walked so far
    ys = {kind: [] for kind in layers}
    for kind, n in runs:
        lo = taken[kind]
        taken[kind] += n
        if scan and n > 1:
            whole = n == pattern.count(kind)
            x, y = jax.lax.scan(layer_fn[kind], x, tree_map(
                lambda a: a if whole else a[lo:lo + n], layers[kind]))
            ys[kind].append(y)
            continue
        for at in range(lo, lo + n):
            x, y = layer_fn[kind](x, tree_map(lambda a: a[at], layers[kind]))
            ys[kind].append(tree_map(lambda a: a[None], y))
    ys = {kind: y[0] if len(y) == 1
          else tree_map(lambda *a: jnp.concatenate(a), *y)
          for kind, y in ys.items() if y}
    return x, ys["layer"] if one_kind else ys


def forward(cfg: LlamaConfig, params: Dict[str, Any], tokens: jax.Array,
            mesh=None) -> jax.Array:
    """tokens [b, s] int32 → logits [b, s, vocab] float32."""
    with jax.named_scope("embed"):
        x = embed_rows(params["embed"], tokens, cfg.dtype, mesh)
        if cfg.embed_scale != 1.0:
            x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
        cos, sin = rope_frequencies(cfg.head_dim_, tokens.shape[1],
                                    cfg.rope_theta, dtype=cfg.dtype,
                                    scaling=cfg.rope_scaling_dict)
    level = (resolve_remat(cfg, LAYER_KINDS, params, tokens, mesh)
             if cfg.remat else None)
    x, _ = run_layers(
        lambda x_, p_: (_layer(cfg, x_, p_, cos, sin, mesh=mesh), None),
        x, params["layers"], level=level, scan=cfg.scan_layers)
    return _final_head(cfg, params, x)


def _final_head(cfg: LlamaConfig, params, x: jax.Array) -> jax.Array:
    """Shared model tail: final norm + (tied) LM head in fp32."""
    with jax.named_scope("head_loss"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps,
                     cfg.zero_centred_norm)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        return jnp.dot(x, head.astype(cfg.dtype),
                       preferred_element_type=jnp.float32)


def cross_entropy_loss(logits: jax.Array, targets: jax.Array,
                       mask: Optional[jax.Array] = None,
                       z_loss: float = 0.0) -> jax.Array:
    """Token-level CE in fp32 with optional z-loss regularization."""
    with jax.named_scope("head_loss"):
        logits = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        true_logit = jnp.take_along_axis(
            logits, targets[..., None], axis=-1
        )[..., 0]
        nll = lse - true_logit
        if z_loss:
            nll = nll + z_loss * jnp.square(lse)
        if mask is not None:
            nll = nll * mask
            return nll.sum() / jnp.maximum(mask.sum(), 1)
        return nll.mean()


def _blocked_head_inputs(cfg: LlamaConfig, params, x: jax.Array
                         ) -> Tuple[jax.Array, jax.Array]:
    """x [b, s, h] (the last layer's output) -> what ``ops/layers``' blocked
    head walks: the normed rows [b * s, h] and the head [h, vocab] in the
    compute dtype."""
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps,
                 cfg.zero_centred_norm)
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(cfg.dtype)
    return x.reshape(-1, x.shape[-1]), head


def blocked_token_nll(cfg: LlamaConfig, params, x: jax.Array,
                      targets: jax.Array, block: Optional[int] = None,
                      logits_divisor: float = 1.0) -> jax.Array:
    """The model's tail where the logits are too large to exist whole:
    x [b, s, h] (the last layer's output), targets [b, s] -> the
    next-token loss of every position [b, s] float32, ``_final_head`` and
    ``cross_entropy_loss``'s arithmetic over ``block`` tokens at a time
    (``ops/layers.blocked_head_nll``; ``head_block`` tokens by default).
    ``logits_divisor``: Granite's ``logits_scaling``."""
    with jax.named_scope("head_loss"):
        rows, head = _blocked_head_inputs(cfg, params, x)
        return blocked_head_nll(
            rows, head, targets.reshape(-1), block=block,
            logits_divisor=logits_divisor).reshape(targets.shape)


def blocked_cross_entropy(cfg: LlamaConfig, params, x: jax.Array,
                          targets: jax.Array,
                          mask: Optional[jax.Array] = None,
                          block: Optional[int] = None,
                          logits_divisor: float = 1.0) -> jax.Array:
    """``cross_entropy_loss`` of ``blocked_token_nll``'s positions, the
    mean or the mask's weighted mean, as a training step differentiates
    it: the positions' weights go into ``ops/layers.blocked_head_loss``,
    whose rule takes a block's gradients while its logits stand."""
    with jax.named_scope("head_loss"):
        rows, head = _blocked_head_inputs(cfg, params, x)
        if mask is None:
            weights = jnp.full(targets.size, 1.0 / targets.size, jnp.float32)
        else:
            mask = mask.astype(jnp.float32)
            weights = (mask / jnp.maximum(mask.sum(), 1)).reshape(-1)
        return blocked_head_loss(rows, head, targets.reshape(-1), weights,
                                 block=block, logits_divisor=logits_divisor)


def loss_fn(cfg: LlamaConfig, params, batch: Dict[str, jax.Array],
            mesh=None) -> jax.Array:
    """batch: {"tokens": [b, s]} — next-token prediction."""
    tokens = batch["tokens"]
    logits = forward(cfg, params, tokens[:, :-1], mesh=mesh)
    mask = batch.get("mask")
    if mask is not None:
        mask = mask[:, 1:]
    return cross_entropy_loss(logits, tokens[:, 1:], mask)


def loss_fn_pp(cfg: LlamaConfig, params, batch: Dict[str, jax.Array],
               mesh, num_microbatches: int) -> jax.Array:
    """Pipeline-parallel next-token loss: the layer stack is sharded over
    the mesh's ``pp`` axis and microbatches flow through a GPipe schedule
    compiled as ONE program (parallel/pipeline.py — shard_map + ppermute
    rotation; jax.grad reverses the schedule for the backward pass).

    Embed/head run replicated across pp (they are fsdp/tp-sharded by the
    usual rules); only the decoder blocks pipeline. num_microbatches must
    divide the batch and should be >> pp to amortize the bubble.
    """
    level = remat_level_without_plan(cfg)
    from jax.sharding import PartitionSpec as P

    # pp x sequence-parallel composition: pp OUTER (this shard_map), sp
    # INNER (ring_attention_local's KV blocks rotate on the sp sub-axis,
    # or ulysses_attention_local's all-to-alls run over it). Sequences
    # shard over sp; rope tables enter as sp-sharded inputs so each rank
    # holds its slice.
    seq_par = cfg.attn_impl in ("ring", "ulysses")
    sp = dict(getattr(mesh, "shape", {})).get("sp", 1)
    if seq_par and sp <= 1:
        raise ValueError(
            f"attn_impl={cfg.attn_impl!r} with pipeline parallelism "
            "requires a mesh with an 'sp' axis (> 1)")
    pp = dict(getattr(mesh, "shape", {})).get("pp", 1)
    if cfg.num_layers % max(pp, 1):
        raise ValueError(
            f"num_layers={cfg.num_layers} must divide the mesh's "
            f"pp={pp} (each stage holds num_layers/pp blocks)")

    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    b, s = inputs.shape
    M = num_microbatches
    assert b % M == 0, f"batch {b} must divide into {M} microbatches"
    x = embed_rows(params["embed"], inputs, cfg.dtype, mesh)
    if cfg.embed_scale != 1.0:
        x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
    cos, sin = rope_frequencies(cfg.head_dim_, s, cfg.rope_theta,
                                dtype=cfg.dtype,
                                scaling=cfg.rope_scaling_dict)
    mbs = x.reshape(M, b // M, s, cfg.hidden_size)

    seq_axis = "sp" if seq_par else None
    if seq_par and s % sp:
        raise ValueError(
            f"sequence length {s} must be divisible by the mesh's "
            f"sp={sp}")

    def stage_fn_with_rope(cos_, sin_):
        def stage_fn(stage_layers, xmb):
            # this stage's L/P layers
            return run_layers(
                lambda x_, p_: (_layer(cfg, x_, p_, cos_, sin_,
                                       seq_axis=seq_axis), None),
                xmb, stage_layers, level=level, scan=cfg.scan_layers)[0]
        return stage_fn

    def sharded_pipeline(stage_layers, mbs_rep, cos_, sin_):
        from ray_tpu.parallel.pipeline import pipeline_apply

        from ray_tpu.parallel.device_collectives import axis_size
        pp = axis_size("pp")
        outs = pipeline_apply(stage_fn_with_rope(cos_, sin_),
                              stage_layers, mbs_rep, "pp")
        # outputs live on the LAST stage; sum-rotate so every stage holds
        # them (cheap: one psum of zeros elsewhere)
        return jax.lax.psum(
            jnp.where(jax.lax.axis_index("pp") == pp - 1, outs, 0.0), "pp")

    layer_spec = P("pp")           # layer dim sharded over pp
    # REAL data parallelism alongside pp: the per-microbatch batch dim
    # shards over the mesh's data axes (each dp group pipelines its own
    # slice); activations stay replicated only across pp. With ring
    # attention the SEQUENCE dim additionally shards over sp, and each
    # rank receives its slice of the rope tables.
    data_axes = tuple(a for a in mesh.axis_names if a in ("dp", "fsdp"))
    mb_spec = P(None, data_axes if data_axes else None,
                "sp" if seq_par else None)
    rope_spec = P("sp" if seq_par else None)
    outs = jax.shard_map(
        sharded_pipeline, mesh=mesh,
        in_specs=(layer_spec, mb_spec, rope_spec, rope_spec),
        out_specs=mb_spec,
        check_vma=False,
    )(params["layers"], mbs, cos, sin)

    x = outs.reshape(b, s, cfg.hidden_size)
    logits = _final_head(cfg, params, x)
    mask = batch.get("mask")
    if mask is not None:
        mask = mask[:, 1:]
    return cross_entropy_loss(logits, targets, mask)


def num_params(params) -> int:
    return sum(p.size for p in jax.tree_util.tree_leaves(params))


def param_shardings(cfg: LlamaConfig, mesh):
    """NamedSharding pytree for params on a given mesh."""
    from ray_tpu.parallel.sharding import shard_pytree_like

    return shard_pytree_like(logical_axes_without_layer(cfg), mesh)


def logical_axes_without_layer(cfg: LlamaConfig):
    """Logical axes with the stacked 'layer' dim mapped to None (pipeline
    parallelism later maps it to 'pp')."""
    return jax.tree_util.tree_map(
        lambda t: tuple(None if a == "layer" else a for a in t),
        logical_axes(cfg),
        is_leaf=lambda x: isinstance(x, tuple),
    )


def init_shapes(cfg: LlamaConfig):
    """ShapeDtypeStruct pytree matching init_params (for eval_shape uses)."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
