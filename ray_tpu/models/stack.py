"""A model as a table of layer kinds, and the one module that walks it.

``Stack(kinds, ...)`` is a model: ``kinds`` maps the names its config's
``pattern`` uses to ``(mixer, mlp)``, two ``ops/layers.Part`` records kept
beside the code they run (``llama.attention_part``,
``ops/conv.short_conv_part``, ``ops/ssm.mamba2_part``,
``ops/delta.gated_delta_part``, ``ops/layers.swiglu_part``,
``ops/moe.routed_part``). Everything else a training loop asks of a model
is here, once: the parameters (``logical_axes``, ``init_params``,
``param_shardings``), the walk (embed, ``llama.resolve_remat``,
``llama.run_layers``, what the layers report put back into layer order),
the head (whole logits, or ``llama.blocked_cross_entropy`` and
``llama.blocked_token_nll`` where they would not fit) and the loss with the
terms the parts add to it, and for routers balanced by a bias no optimizer
owns (``routed_part(bias=True)``) what an optimizer is given and the bias's
move after a step. ``models/laguna.py``, ``lfm2.py``, ``granite.py``
and ``olmo_hybrid.py`` are a config, a table and the names of one
``Stack``'s methods; a new architecture is one more such
module and, where its operator is new, one part under ``ops/``.

Parameters are stacked by kind: ``params["layers"][kind][name]`` is
``[layers of that kind, ...]``, a kind's layers in their order. Training
only: the serving engines walk ``llama``'s stack of one kind.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from ray_tpu.models import llama, mixtral
from ray_tpu.ops import moe
from ray_tpu.ops.layers import Ctx, Part, embed_rows, norm_start


def draw(cfg, key: jax.Array, shape: Tuple[int, ...], start) -> jax.Array:
    """One parameter as its ``Leaf.start`` says, in ``cfg.param_dtype``: a
    fan-in (truncated normal over its root), "ones", "zeros",
    "zeros_float32" (float32 whatever the dtype), ``(lo, hi)`` (``A``
    uniform in that range, stored as its log) or "dt" (log-uniform in
    0.001-0.1 and not under 1e-4, stored as ``dt + log(-expm1(-dt))``, the
    inverse of the softplus: Mamba-2's and the delta-net's published
    initialisation)."""
    dtype = cfg.param_dtype
    if start == "ones":
        return jnp.ones(shape, dtype)
    if start == "zeros":
        return jnp.zeros(shape, dtype)
    if start == "zeros_float32":
        return jnp.zeros(shape, jnp.float32)
    if start == "dt":
        dt = jnp.maximum(1e-4, jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(0.001), math.log(0.1))))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if isinstance(start, tuple):
        return jnp.log(jax.random.uniform(
            key, shape, jnp.float32, *start)).astype(dtype)
    return (jax.random.truncated_normal(key, -3, 3, shape, jnp.float32)
            * (1.0 / math.sqrt(start))).astype(dtype)


def in_layer_order(pattern: Tuple[str, ...], ys: Dict[str, Dict[str, Any]]
                   ) -> Dict[str, Any]:
    """``run_layers``' outputs by kind (``ys[kind][name]``: what the layers
    of a kind reported under a name, stacked) -> name -> the reports of the
    layers that made one, stacked in the stack's order. One kind's stack is
    in that order already."""
    tree_map = jax.tree_util.tree_map
    said = {}
    for name in dict.fromkeys(n for y in ys.values() for n in y):
        of = [kind for kind, y in ys.items() if name in y]
        if len(of) == 1:
            said[name] = ys[of[0]][name]
            continue
        taken, rows = dict.fromkeys(of, 0), []
        for kind in pattern:
            if kind in taken:
                at = taken[kind]
                rows.append(tree_map(lambda a: a[at], ys[kind][name]))
                taken[kind] += 1
        said[name] = tree_map(lambda *a: jnp.stack(a), *rows)
    return said


def rows_held(cfg, expert_counts) -> Any:
    """Of ``expert_counts [Lr, E]``, the rows the held experts multiplied
    (the ``moe_rows_held`` counter; all of them where all are held)."""
    first, count = cfg.experts_held or (0, cfg.num_experts)
    return expert_counts[:, first:first + count].sum()


def rows_passed(cfg, expert_counts) -> int:
    """Of ``expert_counts [Lr, E]`` on the host, the rows the passes over
    the held experts' rows took (the ``moe_rows_passed`` counter,
    ``ops/moe.rows_passed``); ``rows_held`` over it is the passes' fill."""
    return moe.rows_passed(expert_counts, cfg.experts_held,
                           getattr(cfg, "held_headroom", None))


# A router's bias (``routed_part(bias=True)``: the leaf ``router_bias``)
# takes part in the choice alone and gets no gradient: it is in the
# parameter tree and not the optimizer's. ``Stack.update_router_bias``
# moves it after a step.
def trainable(params: Dict[str, Any]) -> Dict[str, Any]:
    """The leaves an optimizer owns: every one but the routers' bias."""
    return {**params, "layers": {
        kind: {k: v for k, v in leaves.items() if k != "router_bias"}
        for kind, leaves in params["layers"].items()}}


def with_trainable(params: Dict[str, Any], trained: Dict[str, Any]
                   ) -> Dict[str, Any]:
    """``params`` with ``trained`` (like ``trainable(params)``) in place
    of the leaves an optimizer owns."""
    return {**trained, "layers": {
        kind: {**params["layers"][kind], **leaves}
        for kind, leaves in trained["layers"].items()}}


def router_bias_abs_max(params: Dict[str, Any]) -> jax.Array:
    """The counter ``moe_router_bias_abs_max``: the largest ``|b|`` of
    any router."""
    return jnp.max(jnp.stack([
        jnp.abs(leaves["router_bias"]).max()
        for leaves in params["layers"].values() if "router_bias" in leaves]))


@dataclass(frozen=True, eq=False)
class Stack:
    """A model: its table of kinds and what the table does not say.
    ``reports``: the name (``Part.reports``) of what ``forward`` and
    ``token_nll`` hand back beside their result: "router", the routed
    layers' statistics, or a mixer's states; several names (a tuple: a
    kind with a linear mixer and a routed MLP) hand back a dict by name.
    ``blocked_head``: the loss
    never builds the logits whole (100,352 rows at 32,768 positions would
    be 13 GB of float32), and the plan is told so: ``loss_terms`` sums it
    block by block with both gradients of a block taken while its logits
    stand (``llama.blocked_cross_entropy``: three ``[block, vocab]``
    products a block), ``token_nll`` hands back every position's loss
    (``llama.blocked_token_nll``: whoever differentiates that pays for a
    block's logits twice). ``embed_scale`` and ``logits_divisor`` name the
    config's fields that multiply the embedding and divide the logits
    (Granite's ``embedding_multiplier`` and ``logits_scaling``). The head is
    the embedding where ``cfg.tie_embeddings``, an ``lm_head`` of its own
    elsewhere."""
    kinds: Dict[str, Tuple[Part, Part]]
    reports: Union[str, Tuple[str, ...]]
    blocked_head: bool = False
    embed_scale: Optional[str] = None
    logits_divisor: Optional[str] = None

    def _leaves(self, cfg) -> Dict[str, Dict[str, Any]]:
        """kind -> name -> ``Leaf``, the kinds in the pattern's order, a
        kind's leaves in its parts'."""
        return {kind: {**self.kinds[kind][0].leaves(cfg),
                       **self.kinds[kind][1].leaves(cfg)}
                for kind in dict.fromkeys(cfg.pattern)}

    def logical_axes(self, cfg) -> Dict[str, Any]:
        return {"embed": ("vocab", "embed"),
                "layers": {kind: {name: ("layer",) + leaf.axes
                                  for name, leaf in leaves.items()}
                           for kind, leaves in self._leaves(cfg).items()},
                "final_norm": ("embed",),
                **({} if cfg.tie_embeddings
                   else {"lm_head": ("embed", "vocab")})}

    def init_params(self, cfg, key: jax.Array) -> Dict[str, Any]:
        """Every leaf as its part says it starts (``draw``); a kind's
        layers stacked in their order. The keys: ``key`` folded with 0 for
        the embedding, with ``n + 1`` for the pattern's ``n``-th kind and
        split over its leaves, with 99 for an ``lm_head``."""
        h, v = cfg.hidden_size, cfg.vocab_size
        layers = {}
        for n, (kind, leaves) in enumerate(self._leaves(cfg).items()):
            depth = cfg.pattern.count(kind)
            keys = jax.random.split(jax.random.fold_in(key, n + 1),
                                    len(leaves))
            layers[kind] = {name: draw(cfg, k, (depth,) + leaf.shape,
                                       leaf.start)
                            for k, (name, leaf) in zip(keys, leaves.items())}
        params = {"embed": draw(cfg, jax.random.fold_in(key, 0), (v, h), h),
                  "layers": layers,
                  "final_norm": draw(cfg, None, (h,), norm_start(cfg))}
        if not cfg.tie_embeddings:
            params["lm_head"] = draw(cfg, jax.random.fold_in(key, 99),
                                     (h, v), h)
        return params

    def param_shardings(self, cfg, mesh):
        from ray_tpu.parallel.sharding import shard_pytree_like

        return shard_pytree_like(
            mixtral.without_layer_axis(self.logical_axes(cfg)), mesh)

    def hidden(self, cfg, params, tokens: jax.Array, mesh=None,
               keep_router_logits: bool = False,
               keep_index_choice: bool = False
               ) -> Tuple[jax.Array, Dict[str, Any]]:
        """tokens [b, s] -> (the last layer's output [b, s, hidden], name
        -> what the layers reported under it, stacked in layer order).
        ``keep_index_choice``: the index layers report their index's
        inputs and their packed choice of keys too (``ops/mla.py``)."""
        pattern = cfg.pattern
        with jax.named_scope("embed"):
            x = embed_rows(params["embed"], tokens, cfg.dtype, mesh)
            if self.embed_scale:
                x = x * jnp.asarray(getattr(cfg, self.embed_scale),
                                    cfg.dtype)
            once = {}
            for kind in dict.fromkeys(pattern):
                for part in self.kinds[kind]:
                    if part.once and part.once not in once:
                        once[part.once] = part.once(cfg, tokens)
        ctx = Ctx(mesh, once, keep_router_logits, keep_index_choice)

        def layer_of(kind):
            mixer, mlp = self.kinds[kind]

            def layer(x_, p_):
                x_, said = mixer.body(cfg, x_, p_, ctx)
                x_, more = mlp.body(cfg, x_, p_, ctx)
                return x_, {**said, **more}
            return layer

        level = llama.resolve_remat(
            cfg, self.kinds, params, tokens, mesh, self.param_shardings,
            pattern=pattern,
            head_tokens=llama.head_block(tokens.size, cfg.vocab_size)
            if self.blocked_head else None) if cfg.remat else None
        x, ys = llama.run_layers(
            {kind: layer_of(kind) for kind in params["layers"]}, x,
            params["layers"], level=level, scan=cfg.scan_layers,
            pattern=pattern)
        return x, in_layer_order(pattern, ys)

    def _said(self, said: Dict[str, Any]) -> Any:
        """What ``forward`` and ``token_nll`` hand back of the layers'
        reports (``reports``)."""
        if isinstance(self.reports, str):
            return said[self.reports]
        return {name: said[name] for name in self.reports}

    def _divisor(self, cfg) -> float:
        return (getattr(cfg, self.logits_divisor) if self.logits_divisor
                else 1.0)

    def _logits(self, cfg, params, x: jax.Array) -> jax.Array:
        logits = llama._final_head(cfg, params, x)
        return logits / self._divisor(cfg) if self.logits_divisor else logits

    def forward(self, cfg, params, tokens: jax.Array, mesh=None,
                keep_router_logits: bool = False
                ) -> Tuple[jax.Array, Any]:
        """tokens [b, s] -> (logits [b, s, vocab] float32, whole, for sizes
        at which they fit; what the layers reported under
        ``self.reports``, in layer order). Of routed layers that is
        ``counts [Lr, E]`` (rows routed to each expert, held or not), with
        a balancing loss ``prob [Lr, E]`` and ``z [Lr]`` and, asked for,
        ``logits [Lr, b * s, E]`` and, where a bias takes part in the
        choice, ``chosen [Lr, b * s, K]``; of a scan or a rule the states
        after the last position ``[L, b, H, ...]`` float32."""
        x, said = self.hidden(cfg, params, tokens, mesh=mesh,
                              keep_router_logits=keep_router_logits)
        return self._logits(cfg, params, x), self._said(said)

    def token_nll(self, cfg, params, tokens: jax.Array, mesh=None,
                  head_block: Optional[int] = None,
                  keep_router_logits: bool = False
                  ) -> Tuple[jax.Array, Any]:
        """tokens [b, s + 1] -> (the next-token loss of every position
        [b, s] float32 through the blocked head, what ``forward`` hands
        back beside its logits)."""
        x, said = self.hidden(cfg, params, tokens[:, :-1], mesh=mesh,
                              keep_router_logits=keep_router_logits)
        return llama.blocked_token_nll(
            cfg, params, x, tokens[:, 1:], block=head_block,
            logits_divisor=self._divisor(cfg)), self._said(said)

    def loss_terms(self, cfg, params, batch: Dict[str, jax.Array], mesh=None
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """(loss, its terms and the parts' counters): the cross entropy, a
        batch's ``mask`` weighting it, plus what each reporting part's
        ``terms`` adds (a routed mixture its balancing loss and the routed
        layers' ``expert_counts [Lr, E]``, a scan or a rule the largest
        ``|S|`` under its counter's name). Made for
        ``jax.value_and_grad(..., has_aux=True)``."""
        tokens, mask = batch["tokens"], batch.get("mask")
        x, said = self.hidden(cfg, params, tokens[:, :-1], mesh=mesh)
        if mask is not None:
            mask = mask[:, 1:]
        if self.blocked_head:
            ce = llama.blocked_cross_entropy(
                cfg, params, x, tokens[:, 1:], mask,
                logits_divisor=self._divisor(cfg))
        else:
            ce = llama.cross_entropy_loss(self._logits(cfg, params, x),
                                          tokens[:, 1:], mask)
        loss, terms = ce, {"cross_entropy": ce}
        reporting = {part.reports: part
                     for kind in dict.fromkeys(cfg.pattern)
                     for part in self.kinds[kind] if part.terms}
        for name, part in reporting.items():
            more, counters = part.terms(cfg, said[name])
            if more is not None:
                loss = loss + more
            terms.update(counters)
        return loss, terms

    def loss_fn(self, cfg, params, batch: Dict[str, jax.Array], mesh=None
                ) -> jax.Array:
        return self.loss_terms(cfg, params, batch, mesh=mesh)[0]

    def update_router_bias(self, cfg, params: Dict[str, Any],
                           expert_counts: jax.Array) -> Dict[str, Any]:
        """``params`` after a step whose routed layers, in their order,
        sent ``expert_counts [Lr, E]`` rows to each expert: every router's
        bias moves ``cfg.bias_update_rate`` toward the experts that got
        fewer rows than the mean, away from those that got more (loss-free
        balancing, arXiv:2408.15664). On a mesh the counts are the whole
        batch's (``forward`` sums them over the batch axes)."""
        with jax.named_scope("moe_route"), \
                jax.named_scope("moe_bias_update"):
            c = expert_counts.astype(jnp.float32)
            move = cfg.bias_update_rate * jnp.sign(
                c.mean(-1, keepdims=True) - c)                  # [Lr, E]
            # kind -> of the routed layers in their order, that kind's
            at: Dict[str, list] = {}
            routed = [kind for kind in cfg.pattern
                      if self.kinds[kind][1].reports == "router"]
            for row, kind in enumerate(routed):
                at.setdefault(kind, []).append(row)
            return {**params, "layers": {
                kind: ({**leaves, "router_bias": leaves["router_bias"]
                        + move[jnp.asarray(at[kind])]}
                       if kind in at else leaves)
                for kind, leaves in params["layers"].items()}}
