"""A model as a table of layer kinds, and the one module that walks it.

``Stack(kinds, ...)`` is a model: ``kinds`` maps the names its config's
``pattern`` uses to a layer's parts in the order they run, ``(mixer, mlp)``
or the one part of a layer that is one sum (``nemotron_h``: a scan, an
attention or a mixture), ``ops/layers.Part`` records kept
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
move after a step, and a multi-token prediction module beside the head
(``Stack(mtp=..)``). ``models/laguna.py``, ``lfm2.py``, ``granite.py``,
``olmo_hybrid.py``, ``deepseek_v2.py``, ``dots3.py``, ``qwen3_next.py`` and
``nemotron_h.py`` are a config, a table and the names of one
``Stack``'s methods; a new architecture is one more such
module and, where its operator is new, one part under ``ops/``.

Parameters are stacked by kind: ``params["layers"][kind][name]`` is
``[layers of that kind, ...]``, a kind's layers in their order; a
prediction module's are ``params["mtp"]`` (``Stack._mtp_hidden``). Training
only: the serving engines walk ``llama``'s stack of one kind.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from ray_tpu.models import llama, mixtral
from ray_tpu.ops import moe
from ray_tpu.ops.layers import Ctx, Part, embed_rows, norm_start, rms_norm
from ray_tpu.util import tracing


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
    """The leaves an optimizer owns: every one but the routers' bias (a
    prediction module's layers as the stack's)."""
    out = {**params, "layers": {
        kind: {k: v for k, v in leaves.items() if k != "router_bias"}
        for kind, leaves in params["layers"].items()}}
    if "mtp" in params:
        out["mtp"] = trainable(params["mtp"])
    return out


def with_trainable(params: Dict[str, Any], trained: Dict[str, Any]
                   ) -> Dict[str, Any]:
    """``params`` with ``trained`` (like ``trainable(params)``) in place
    of the leaves an optimizer owns."""
    out = {**trained, "layers": {
        kind: {**params["layers"][kind], **leaves}
        for kind, leaves in trained["layers"].items()}}
    if "mtp" in trained:
        out["mtp"] = with_trainable(params["mtp"], trained["mtp"])
    return out


def _layers_of(params: Dict[str, Any]):
    """The stacked leaves by kind of the stack and, where there is one, of
    the prediction module after them."""
    yield from params["layers"].values()
    if "mtp" in params:
        yield from params["mtp"]["layers"].values()


def router_bias_abs_max(params: Dict[str, Any]) -> jax.Array:
    """The counter ``moe_router_bias_abs_max``: the largest ``|b|`` of
    any router."""
    return jnp.max(jnp.stack([
        jnp.abs(leaves["router_bias"]).max()
        for leaves in _layers_of(params) if "router_bias" in leaves]))


# how many tokens ahead a prediction module's target lies (looked up at
# trace time: ``benchmark/tests/scan_moe_limits.py`` plants 1, the head's own)
MTP_AHEAD = 2


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
    elsewhere. ``mtp`` names the config's field that holds the kinds of
    a multi-token prediction module's layers in their order (none where it
    is empty): depth 1 of DeepSeek-V3's section 2.2. On positions with both
    targets, ``h' = [N_e(embed[t_{i+1}]) ; N_h(x_L,i)] W_eh`` (``x_L``
    before the last norm), the module's own layers of those kinds, then the
    model's own head behind a norm of the module's, the target ``t_{i+2}``:
    ``loss_terms`` takes ``seq + 2`` ids a row, runs the head twice and
    adds ``cfg.mtp_loss_scale`` times the module's cross entropy
    (``mtp_cross_entropy``); the module's routers report after the
    stack's, and its layers take the remat level of their kinds. ``forward``
    and ``token_nll`` stay the main model's."""
    kinds: Dict[str, Tuple[Part, ...]]
    reports: Union[str, Tuple[str, ...]]
    blocked_head: bool = False
    embed_scale: Optional[str] = None
    logits_divisor: Optional[str] = None
    mtp: Optional[str] = None

    def _mtp_pattern(self, cfg) -> Tuple[str, ...]:
        return tuple(getattr(cfg, self.mtp)) if self.mtp else ()

    def _leaves(self, cfg, pattern=None) -> Dict[str, Dict[str, Any]]:
        """kind -> name -> ``Leaf``, the kinds in the pattern's order, a
        kind's leaves in its parts'."""
        return {kind: {name: leaf for part in self.kinds[kind]
                       for name, leaf in part.leaves(cfg).items()}
                for kind in dict.fromkeys(
                    cfg.pattern if pattern is None else pattern)}

    def logical_axes(self, cfg) -> Dict[str, Any]:
        def stacked(pattern):
            return {kind: {name: ("layer",) + leaf.axes
                           for name, leaf in leaves.items()}
                    for kind, leaves in self._leaves(cfg, pattern).items()}

        module = self._mtp_pattern(cfg)
        return {"embed": ("vocab", "embed"),
                "layers": stacked(cfg.pattern),
                "final_norm": ("embed",),
                **({} if cfg.tie_embeddings
                   else {"lm_head": ("embed", "vocab")}),
                **({"mtp": {"embed_norm": ("embed",),
                            "hidden_norm": ("embed",),
                            "join": ("mlp", "embed"),
                            "layers": stacked(module),
                            "final_norm": ("embed",)}} if module else {})}

    def init_params(self, cfg, key: jax.Array) -> Dict[str, Any]:
        """Every leaf as its part says it starts (``draw``); a kind's
        layers stacked in their order. The keys: ``key`` folded with 0 for
        the embedding, with ``n + 1`` for the pattern's ``n``-th kind and
        split over its leaves, with 99 for an ``lm_head``; a prediction
        module's kinds with ``101 + n``, its joining matrix with 100."""
        h, v = cfg.hidden_size, cfg.vocab_size

        def stacked(pattern, first):
            layers = {}
            for n, (kind, leaves) in enumerate(
                    self._leaves(cfg, pattern).items()):
                depth = pattern.count(kind)
                keys = jax.random.split(jax.random.fold_in(key, first + n),
                                        len(leaves))
                layers[kind] = {
                    name: draw(cfg, k, (depth,) + leaf.shape, leaf.start)
                    for k, (name, leaf) in zip(keys, leaves.items())}
            return layers

        ones = norm_start(cfg)
        params = {"embed": draw(cfg, jax.random.fold_in(key, 0), (v, h), h),
                  "layers": stacked(cfg.pattern, 1),
                  "final_norm": draw(cfg, None, (h,), ones)}
        if not cfg.tie_embeddings:
            params["lm_head"] = draw(cfg, jax.random.fold_in(key, 99),
                                     (h, v), h)
        module = self._mtp_pattern(cfg)
        if module:
            params["mtp"] = {
                "embed_norm": draw(cfg, None, (h,), ones),
                "hidden_norm": draw(cfg, None, (h,), ones),
                "join": draw(cfg, jax.random.fold_in(key, 100), (2 * h, h),
                             2 * h),
                "layers": stacked(module, 101),
                "final_norm": draw(cfg, None, (h,), ones)}
        return params

    def param_shardings(self, cfg, mesh):
        from ray_tpu.parallel.sharding import shard_pytree_like

        return shard_pytree_like(
            mixtral.without_layer_axis(self.logical_axes(cfg)), mesh)

    def hidden(self, cfg, params, tokens: jax.Array, mesh=None,
               keep_router_logits: bool = False,
               keep_index_choice: bool = False, positions=None
               ) -> Tuple[jax.Array, Dict[str, Any]]:
        """tokens [b, s] -> (the last layer's output [b, s, hidden], name
        -> what the layers reported under it, stacked in layer order).
        ``keep_index_choice``: the index layers report their index's
        inputs and their packed choice of keys too (``ops/mla.py``).
        ``positions`` [streams, b, s]: the batch's own positions, for a
        model whose rope reads them (``Part.once`` is then called with
        them: ``ops/layers.mrope_frequencies``); None: positions ``0 .. s -
        1``, what every ``once`` assumes without."""
        return self._walk(cfg, params, tokens, mesh, keep_router_logits,
                          keep_index_choice, positions=positions)[:2]

    def _embed(self, cfg, params, tokens, mesh) -> jax.Array:
        x = embed_rows(params["embed"], tokens, cfg.dtype, mesh)
        if self.embed_scale:
            x = x * jnp.asarray(getattr(cfg, self.embed_scale), cfg.dtype)
        return x

    def _layer_of(self, cfg, kind: str, ctx: Ctx):
        """One layer of ``kind`` for ``llama.run_layers``: its parts in
        their order."""
        def layer(x_, p_):
            said = {}
            for part in self.kinds[kind]:
                x_, more = part.body(cfg, x_, p_, ctx)
                said = {**said, **more}
            return x_, said
        return layer

    def _walk(self, cfg, params, tokens, mesh, keep_router_logits=False,
              keep_index_choice=False, module: Tuple[str, ...] = (),
              positions=None):
        """``hidden`` and beside its two results what a prediction module
        run after it shares with it: the parts' context and the remat
        level. ``module``: the module's kinds, whose layers the plan then
        reckons as further layers of the stack."""
        pattern = cfg.pattern
        with jax.named_scope("embed"):
            x = self._embed(cfg, params, tokens, mesh)
            once = {}
            for kind in dict.fromkeys(pattern + module):
                for part in self.kinds[kind]:
                    if part.once and part.once not in once:
                        once[part.once] = (
                            part.once(cfg, tokens) if positions is None
                            else part.once(cfg, tokens, positions))
        ctx = Ctx(mesh, once, keep_router_logits, keep_index_choice)
        planned = params if not module else {**params, "layers": {
            **params["mtp"]["layers"], **params["layers"]}}
        level = llama.resolve_remat(
            cfg, self.kinds, planned, tokens, mesh, self.param_shardings,
            pattern=pattern + module,
            head_tokens=llama.head_block(tokens.size, cfg.vocab_size)
            if self.blocked_head else None) if cfg.remat else None
        x, ys = llama.run_layers(
            {kind: self._layer_of(cfg, kind, ctx)
             for kind in params["layers"]}, x,
            params["layers"], level=level, scan=cfg.scan_layers,
            pattern=pattern)
        return x, in_layer_order(pattern, ys), ctx, level

    def _mtp_hidden(self, cfg, params, x: jax.Array, following: jax.Array,
                    ctx: Ctx, level) -> Tuple[jax.Array, Dict[str, Any]]:
        """The prediction module before its head: x [b, s, hidden] (the
        last layer's output, before the last norm) and ``following [b,
        s]`` (each position's next token) -> (the module's last layer's
        output, what its layers reported, in their order)."""
        module, m = self._mtp_pattern(cfg), params["mtp"]
        norm = partial(rms_norm, eps=cfg.rms_norm_eps,
                       zero_centred=cfg.zero_centred_norm)
        with jax.named_scope("mtp_join"):
            joined = jnp.concatenate(
                [norm(self._embed(cfg, params, following, ctx.mesh),
                      m["embed_norm"]), norm(x, m["hidden_norm"])], axis=-1)
            h = jnp.dot(joined, m["join"].astype(cfg.dtype),
                        preferred_element_type=jnp.float32
                        ).astype(cfg.dtype)
        h, ys = llama.run_layers(
            {kind: self._layer_of(cfg, kind, ctx) for kind in m["layers"]},
            h, m["layers"], level=level, scan=cfg.scan_layers,
            pattern=module)
        return h, in_layer_order(module, ys)

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
                keep_router_logits: bool = False, positions=None
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
                              keep_router_logits=keep_router_logits,
                              positions=positions)
        return self._logits(cfg, params, x), self._said(said)

    def token_nll(self, cfg, params, tokens: jax.Array, mesh=None,
                  head_block: Optional[int] = None,
                  keep_router_logits: bool = False, positions=None
                  ) -> Tuple[jax.Array, Any]:
        """tokens [b, s + 1] -> (the next-token loss of every position
        [b, s] float32 through the blocked head, what ``forward`` hands
        back beside its logits). ``positions`` [streams, b, s]:
        ``hidden``'s."""
        x, said = self.hidden(cfg, params, tokens[:, :-1], mesh=mesh,
                              keep_router_logits=keep_router_logits,
                              positions=positions)
        return llama.blocked_token_nll(
            cfg, params, x, tokens[:, 1:], block=head_block,
            logits_divisor=self._divisor(cfg)), self._said(said)

    def _both_heads(self, cfg, params, tokens: jax.Array, mesh, head,
                    keep_router_logits: bool = False, positions=None):
        """The stack, its head and, where the config names one, the
        prediction module and the head once more. tokens [b, s + 1], or [b,
        s + 2] with a module, so that each of the ``s`` positions has
        both its targets; ``head(params, x, lo, hi)`` is given the last
        layer's output and where its targets lie in ``tokens``, for the
        module under the scopes ``mtp`` / ``mtp_head`` and with the
        module's last norm in place of the model's: the embedding and the
        head are the model's own. -> (the head's result, the module's or
        None, the layers' reports, the module's after the stack's)."""
        module = self._mtp_pattern(cfg)
        ahead, length = (2 if module else 1), tokens.shape[1]
        x, said, ctx, level = self._walk(
            cfg, params, tokens[:, :-ahead], mesh,
            keep_router_logits=keep_router_logits, module=module,
            positions=positions)
        main = head(params, x, 1, length - ahead + 1)
        if not module:
            return main, None, said
        with tracing.span("rtpu.train.mtp_plan", keep=True, depth=1,
                          pattern=list(module),
                          loss_scale=cfg.mtp_loss_scale, head_shared=True):
            pass
        with jax.named_scope("mtp"):
            h, more = self._mtp_hidden(cfg, params, x, tokens[:, 1:-1], ctx,
                                       level)
            with jax.named_scope("mtp_head"):
                beside = head(
                    {**params, "final_norm": params["mtp"]["final_norm"]},
                    h, MTP_AHEAD, length - 2 + MTP_AHEAD)
        return main, beside, {
            name: (jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b]), said[name], more[name])
                if name in more else said[name]) for name in said}

    def token_nlls(self, cfg, params, tokens: jax.Array, mesh=None,
                   head_block: Optional[int] = None,
                   keep_router_logits: bool = False
                   ) -> Tuple[jax.Array, Optional[jax.Array], Any]:
        """``token_nll`` of both heads of a stack with a prediction module:
        tokens [b, s + 2] -> (the next-token loss of every position [b, s],
        the module's loss of the token after it [b, s], the layers' reports
        with the module's after the stack's)."""
        def nll(params_, x_, lo, hi):
            return llama.blocked_token_nll(
                cfg, params_, x_, tokens[:, lo:hi], block=head_block,
                logits_divisor=self._divisor(cfg))

        main, beside, said = self._both_heads(
            cfg, params, tokens, mesh, nll, keep_router_logits)
        return main, beside, self._said(said)

    def loss_terms(self, cfg, params, batch: Dict[str, jax.Array], mesh=None
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """(loss, its terms and the parts' counters): the cross entropy, a
        batch's ``mask`` weighting it (0 on a target that is not text) and
        its ``positions`` [streams, b, s] where it brings them reaching the
        rope (``hidden``), plus what each reporting part's
        ``terms`` adds (a routed mixture its balancing loss and the routed
        layers' ``expert_counts [Lr, E]``, a scan or a rule the largest
        ``|S|`` under its counter's name). Made for
        ``jax.value_and_grad(..., has_aux=True)``."""
        tokens, mask = batch["tokens"], batch.get("mask")

        def cross_entropy(params_, x_, lo, hi):
            targets = tokens[:, lo:hi]
            weights = None if mask is None else mask[:, lo:hi]
            if self.blocked_head:
                return llama.blocked_cross_entropy(
                    cfg, params_, x_, targets, weights,
                    logits_divisor=self._divisor(cfg))
            return llama.cross_entropy_loss(
                self._logits(cfg, params_, x_), targets, weights)

        ce, more_ce, said = self._both_heads(
            cfg, params, tokens, mesh, cross_entropy,
            positions=batch.get("positions"))
        loss, terms = ce, {"cross_entropy": ce}
        if more_ce is not None:
            loss = loss + cfg.mtp_loss_scale * more_ce
            terms["mtp_cross_entropy"] = more_ce
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
        batch's (``forward`` sums them over the batch axes). A kind may be
        one part or two: a routed layer is one with a part that reports
        under "router"."""
        with jax.named_scope("moe_route"), \
                jax.named_scope("moe_bias_update"):
            c = expert_counts.astype(jnp.float32)
            move = cfg.bias_update_rate * jnp.sign(
                c.mean(-1, keepdims=True) - c)                  # [Lr, E]
            def moved(layers, pattern, first):
                # kind -> of the routed layers in their order, that kind's
                at: Dict[str, list] = {}
                routed = [kind for kind in pattern
                          if any(part.reports == "router"
                                 for part in self.kinds[kind])]
                for row, kind in enumerate(routed, first):
                    at.setdefault(kind, []).append(row)
                return {kind: ({**leaves,
                                "router_bias": leaves["router_bias"]
                                + move[jnp.asarray(at[kind])]}
                               if kind in at else leaves)
                        for kind, leaves in layers.items()}, len(routed)

            layers, rows = moved(params["layers"], cfg.pattern, 0)
            out, module = {**params, "layers": layers}, self._mtp_pattern(cfg)
            if module:
                # a prediction module's routers: the rows after the stack's
                out["mtp"] = {**params["mtp"], "layers": moved(
                    params["mtp"]["layers"], module, rows)[0]}
            return out
