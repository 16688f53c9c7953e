"""The index's scores, or the attention over its choice (``--attend``,
below), alone on the chip, one process. The scores:
``ops/dsa.index_scores`` forward and backward over one layer's walk at a
cell's shapes (``train-dots3-1chip``'s by default: 16,384 positions, 64
index heads of 128, blocks of 256 queries in four tiers of keys, two such
layers, bfloat16), milliseconds a layer, for XLA's form and for the
kernels over their constants, and how far each form's scores and
gradients lie from XLA's form in float32 at the highest matmul precision.

    python3 ray_tpu/tools/index_sweep.py [--kernels 512,64 ...] \
        [--block 256] [--seq 16384]

- ``forward_ms``: every block of the walk scored against its tier's keys,
  the kernels told where the block's diagonal lies (``dsa._scores``), as
  the walk calls them; ``forward_all_tiles_ms``: the same with every tile
  scored (``index_scores`` as the cell's check calls it).
- ``backward_ms``: every block's three gradients from a float32 cotangent
  ``[block, keys]`` that is zero off a planted choice (a query's
  ``--topk`` keys); the forward call is not in it (its residuals are its
  inputs).
- ``step_ms``: what a train step spends in them over ``--layers`` layers,
  three forwards and a backward a layer (the forward, the layer's remat and
  the one the index term's gradient forms in the walk's backward).

``--kernels tile,rows`` sets ``SCORE_TILE`` and ``SCORE_ROWS`` and may be
given again; without it the module's constants are read alone. Every array
is an argument of the jitted call; a time is the wall clock around
``block_until_ready`` of one layer's walk (the device is busy all through
it: a call a block back to back), the median of ``--calls`` after two warm
calls. Prints a line a reading and writes all of them to
``chiprun_out/<--out>`` (``index_sweep.json``). Run as a file; a time read
on the CPU is no device number (the kernels then run in the Pallas
interpreter: use a short ``--seq``).

    python3 ray_tpu/tools/index_sweep.py --attend 512,512,16 [--attend ..] \
        [--block 256] [--no-xla] [--no-gaps]

``--attend tile,rows[,unroll]`` sweeps the attention's calls instead
(``sweep_attend``: ``ATTEND_TILE``, ``ATTEND_ROWS``, ``ATTEND_UNROLL``; 16
heads of ``--widths`` 128,64,128), XLA's form beside them, into
``chiprun_out/attend_sweep.json``.

    python3 ray_tpu/tools/index_sweep.py --block 128 --block 256 --block 512

``--block`` given more than once sweeps the queries a block of the walk
(``sweep_blocks``): at each, the scores' calls, the choice (``dsa.choose``,
forward alone: it has no gradient) and the attention's calls, each in a
walk of its own at the module's constants (or the one ``--kernels`` and
``--attend`` given), the kernels alone; then a table of ms a step and what
``dsa.walk_needs`` says each call holds in VMEM, into
``chiprun_out/block_sweep.json``; beside them ``walk``, the op whole
(``dsa.sparse_attention``'s forward and gradient: the three parts, the
index's term and the sums of the blocks' ``dk`` and ``dv`` into their
tiers'). The blocks are taken as given: the plan's guard
(``dsa.VMEM_CEILING``) is lifted for the sweep.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _ms(fn, xs, calls: int) -> float:
    """Median wall ms of ``calls`` calls of ``fn(*xs)`` after two warm."""
    import jax

    times = []
    for _ in range(calls + 2):
        t = time.perf_counter()
        jax.block_until_ready(fn(*xs))
        times.append((time.perf_counter() - t) * 1e3)
    return statistics.median(times[2:])


def _gap(got, want) -> float:
    import jax.numpy as jnp

    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _write(out, name: str) -> None:
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", name), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--block", type=int, action="append", default=[],
                    help="queries a block of the walk (256); given again: "
                         "the scores, the choice and the attention at each")
    ap.add_argument("--tiers", type=int, default=4)
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--calls", type=int, default=7)
    ap.add_argument("--kernels", action="append", default=[])
    ap.add_argument("--attend", action="append", default=[],
                    help="tile,rows of the attention's kernels: sweeps the "
                         "attention over the choice in the scores' place")
    ap.add_argument("--attend-heads", type=int, default=16)
    ap.add_argument("--widths", default="128,64,128",
                    help="d_n,d_r,d_v of the attention")
    ap.add_argument("--no-gaps", action="store_true")
    ap.add_argument("--no-xla", action="store_true")
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    _interpret_on_the_cpu()
    blocks = a.block or [256]
    if len(blocks) > 1:
        _write(sweep_blocks(a, blocks), a.out or "block_sweep.json")
    elif a.attend:
        _write(sweep_attend(a, blocks[0]), a.out or "attend_sweep.json")
    else:
        _write(sweep_scores(a, blocks[0]), a.out or "index_sweep.json")


def _interpret_on_the_cpu() -> None:
    """On the CPU the kernels run in the Pallas interpreter and the plans
    are a TPU backend's (XLA's forms are called by name and stay XLA's)."""
    import jax

    from ray_tpu.ops import dsa

    if jax.default_backend() == "cpu":
        dsa.score_kernels = functools.partial(dsa.score_kernels,
                                              interpret=True)
        dsa.attend_kernels = functools.partial(dsa.attend_kernels,
                                               interpret=True)
        jax.default_backend = lambda: "tpu"


def sweep_blocks(a, blocks) -> dict:
    """The walk's three parts at each of ``blocks`` queries a block, the
    kernels alone: ms a step over ``--layers`` layers for each, their sum,
    and the calls' VMEM by ``dsa.walk_needs``."""
    import jax.numpy as jnp

    from ray_tpu.ops import dsa

    a.no_xla = a.no_gaps = True
    a.kernels = a.kernels[:1]
    a.attend = a.attend[:1] or [
        f"{dsa.ATTEND_TILE},{dsa.ATTEND_ROWS},{dsa.ATTEND_UNROLL}"]
    dn, dr, dv = (int(x) for x in a.widths.split(","))
    widths = dsa.Widths(a.attend_heads, dn, dr, dv, a.heads, a.dim,
                        jnp.bfloat16)
    out = {"blocks": {}}
    names = ("dsa_scores", "dsa_select", "flash_sparse", "walk")
    for block in blocks:
        (scores,) = sweep_scores(a, block)["kernels"].values()
        attend = sweep_attend(a, block)
        (attended,) = attend["kernels"].values()
        select = sweep_select(a, block)
        parts = dict(zip(names, (scores, select, attended)))
        out["blocks"][block] = dict(
            parts, walk=sweep_walk(a, block, widths),
            device=attend["device"], tiers=attend["tiers"],
            vmem_need_bytes=dsa.walk_needs(
                block, a.seq // attend["tiers"], widths),
            step_ms=(sum(p["step_ms"] for p in parts.values())
                     if all("step_ms" in p for p in parts.values())
                     else None))
    print("| queries a block | " + " | ".join(
        f"{name} ms a step" for name in names) + " | the parts together |")
    print("|---" * (len(names) + 2) + "|")
    for block, row in out["blocks"].items():
        print(f"| {block} | " + " | ".join(
            f"{row[name]['step_ms']:.1f}" if "step_ms" in row[name]
            else "refused" for name in names)
            + f" | {row['step_ms'] and round(row['step_ms'], 1)} |")
    return out


def sweep_walk(a, block: int, widths) -> dict:
    """The op whole, one layer: ``dsa.sparse_attention`` forward and the
    gradient of ``sum(out * do) + kl`` to its seven inputs at ``block``
    queries a block (the guard lifted); ``step_ms`` = ``--layers`` x
    (``forward_ms`` + ``grad_ms``): the forward, the layer's remat of it
    and the walk's backward, which runs no block's forward again (PR
    58)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import dsa

    dsa.VMEM_CEILING = 1 << 40
    H, dn, dr, dv, J, di, dtype = widths
    s, f32 = a.seq, jnp.float32
    ks = jax.random.split(jax.random.PRNGKey(2), 8)
    shapes = ((s, H, dn + dr), (s, H, dn), (s, H, dv), (s, dr), (s, J, di),
              (s, di), (s, H, dv))
    q, kn, v, kr, q_i, k_i, do = (
        jax.random.normal(k, (1,) + shape, f32).astype(dtype)
        for k, shape in zip(ks, shapes))
    w = jax.random.normal(ks[7], (1, s, J), f32) * (J * di) ** -0.5
    how = dict(scale=(dn + dr) ** -0.5, topk=a.topk, block=block,
               tiers=a.tiers)

    def forward(do, *xs):
        out, kl, _ = dsa.sparse_attention(*xs, **how)
        return (out.astype(f32) * do.astype(f32)).sum() + kl.sum()

    xs = (do, q, kn, v, kr, q_i, k_i, w)
    out = {}
    try:
        out["forward_ms"] = _ms(jax.jit(forward), xs, a.calls)
        out["grad_ms"] = _ms(jax.jit(jax.grad(
            forward, argnums=tuple(range(1, 8)))), xs, a.calls)
        out["step_ms"] = a.layers * (out["forward_ms"] + out["grad_ms"])
    except Exception as e:  # noqa: BLE001 (a block Mosaic refuses)
        out["error"] = f"{type(e).__name__}: {str(e)[:600]}"
    print(json.dumps({"walk": dict(out, block=block)}), flush=True)
    return out


def sweep_select(a, block: int) -> dict:
    """The choice alone: ``dsa.choose`` of every block of the walk over its
    tier's keys from float32 scores ``[block, keys]`` (one array of them,
    moved by the block's place so that no two blocks' are equal), forward
    alone; ``step_ms`` = ``--layers`` x 2 ``forward_ms`` (the forward and
    the layer's remat: the walk's backward reads the kept choice)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import dsa

    s = a.seq
    block, tiers = dsa.walk_plan(s, block, a.tiers)
    per_tier = s // block // tiers
    scores = jax.random.normal(jax.random.PRNGKey(1), (block, s),
                               jnp.float32)
    firsts = (jnp.arange(s // block, dtype=jnp.int32) * block
              ).reshape(tiers, per_tier)

    def forward(scores, firsts):
        total = 0
        for t in range(tiers):
            end = (t + 1) * per_tier * block
            total = total + jax.lax.map(
                lambda first, end=end: dsa.choose(
                    scores[:, :end] + first.astype(jnp.float32) * 1e-6,
                    first, a.topk).sum(dtype=jnp.int32), firsts[t]).sum()
        return total

    out = {"forward_ms": _ms(jax.jit(forward), (scores, firsts), a.calls)}
    out["step_ms"] = a.layers * 2 * out["forward_ms"]
    print(json.dumps({"select": dict(out, block=block)}), flush=True)
    return out


def sweep_attend(a, block: int) -> dict:
    """The attention over the choice alone (``dsa.attend_kernels`` beside
    ``dsa.plain_attend``): one layer's walk with the blocks under
    ``jax.checkpoint`` in ``lax.map`` a tier (as ``dsa._walk`` made it
    before it kept a block's forward, PR 58), the choice
    planted (``--topk`` keys a query at random among the causal ones).
    ``forward_ms``: out and the heads' summed probabilities of every block;
    ``grad_ms``: the four gradients of this walk (a forward, the blocks'
    forward again, their backward, and the sums of the blocks' ``dk`` and
    ``dv`` into the tiers'); ``backward_ms`` = ``grad_ms`` - 2
    ``forward_ms``; ``step_ms`` = ``--layers`` x ``grad_ms``: two forwards
    (the forward, the layer's remat) and a backward a layer, what
    ``dsa._walk`` runs now."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import dsa

    f32, bf16 = jnp.float32, jnp.bfloat16
    s, H = a.seq, a.attend_heads
    dn, dr, dv = (int(x) for x in a.widths.split(","))
    scale = (dn + dr) ** -0.5
    block, tiers = dsa.walk_plan(s, block, a.tiers)
    per_tier = s // block // tiers
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(ks[0], (s, H, dn + dr), f32)
    kn = jax.random.normal(ks[1], (s, H, dn), f32)
    v = jax.random.normal(ks[2], (s, H, dv), f32)
    kr = jax.random.normal(ks[3], (s, dr), f32)
    do = jax.random.normal(ks[4], (s, H, dv), f32)
    planted = jax.random.uniform(ks[5], (block, s)) < a.topk / s

    def chosen_of(planted, first, end):
        t = first + jnp.arange(block, dtype=jnp.int32)[:, None]
        key = jnp.arange(end, dtype=jnp.int32)[None, :]
        return (planted[:, :end] | (key == t)) & (key <= t)

    def walks(tile):
        """Jitted: one layer's walk forward and its gradients; ``tile``
        None is XLA's form. New functions every time (jit traces anew)."""
        at = int(tile is not None)

        def loss(q, kn, v, kr, do, planted):
            if at:
                q, kn, v = (jnp.swapaxes(x, 0, 1) for x in (q, kn, v))
                vt = jnp.swapaxes(v, 1, 2)
                do = jnp.transpose(do, (1, 2, 0))       # [H, d_v, s]

            def by_block(x, at=at):
                x = x.reshape(x.shape[:at] + (tiers, per_tier, block)
                              + x.shape[at + 1:])
                return jnp.moveaxis(x, (at, at + 1), (0, 1)) if at else x

            def one_block(keys, end, args):
                q_b, do_b, first = args
                chosen = chosen_of(planted, first, end)
                if at:
                    out, _, p = dsa.attend_kernels(q_b, *keys[:3], chosen,
                                                first, scale, tile, keys[3])
                else:
                    out, p = dsa.plain_attend(q_b, *keys, chosen, scale)
                    p = p.sum(0)
                p = jax.lax.stop_gradient(p)
                return ((out.astype(f32) * do_b.astype(f32)).sum()
                        + (p / p.sum(-1, keepdims=True)).max())

            firsts = (jnp.arange(s // block, dtype=jnp.int32) * block
                      ).reshape(tiers, per_tier)
            total = 0.0
            for g in range(tiers):
                end = (g + 1) * per_tier * block
                keys = (jax.lax.slice_in_dim(kn, 0, end, axis=at),
                        jax.lax.slice_in_dim(v, 0, end, axis=at), kr[:end]
                        ) + ((vt[..., :end],) if at else ())
                total = total + jax.lax.map(
                    jax.checkpoint(lambda x, keys=keys, end=end:
                                   one_block(keys, end, x)),
                    (by_block(q)[g], by_block(do, 2 * at)[g], firsts[g])
                ).sum()
            return total

        return jax.jit(loss), jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))

    # one block against every key, the last block of the sequence
    rows = slice(s - block, s)
    chosen = chosen_of(planted, s - block, s)

    def one_block(tile, q_b, kn, v, kr, do_b):
        def outputs(q_b, kn, v, kr):
            if tile is None:
                out, p = dsa.plain_attend(q_b, kn, v, kr, chosen, scale)
                return out, p.sum(0)
            out, _, p = dsa.attend_kernels(
                *(jnp.swapaxes(x, 0, 1) for x in (q_b, kn, v)), kr, chosen,
                None, scale, tile)
            return jnp.transpose(out, (2, 0, 1)), p

        (out, p), vjp = jax.vjp(outputs, q_b, kn, v, kr)
        return (out, p) + vjp((do_b.astype(out.dtype), jnp.zeros_like(p)))

    want = None
    if not a.no_gaps:
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(jax.jit(functools.partial(
                one_block, None))(q[rows], kn, v, kr, do[rows]))

    def reading(tile):
        forward, grad = walks(tile)
        half = tuple(x.astype(bf16) for x in (q, kn, v, kr, do)) + (planted,)
        out = {}
        try:
            out["forward_ms"] = _ms(forward, half, a.calls)
            out["grad_ms"] = _ms(grad, half, a.calls)
            out["backward_ms"] = out["grad_ms"] - 2 * out["forward_ms"]
            out["step_ms"] = a.layers * out["grad_ms"]
            if want is not None:
                got = jax.jit(functools.partial(one_block, tile))(
                    *(x.astype(bf16) for x in (q[rows], kn, v, kr, do[rows])))
                out["gap_to_float32"] = {
                    n: _gap(x, y) for n, x, y in zip(
                        ("out", "p_sum", "dq", "dk_n", "dv", "dk_r"), got,
                        want)}
        except Exception as e:  # noqa: BLE001 (a setting Mosaic refuses)
            out["error"] = f"{type(e).__name__}: {str(e)[:600]}"
        return out

    out = {"device": jax.devices()[0].device_kind, "seq": s, "heads": H,
           "widths": [dn, dr, dv], "block": block, "tiers": tiers,
           "calls": a.calls,
           "pairs_in_the_tiers": sum(
               per_tier * block * (t + 1) * per_tier * block
               for t in range(tiers)),
           "xla": {} if a.no_xla else reading(None), "kernels": {}}
    print(json.dumps({"xla": out["xla"]}), flush=True)
    for text in a.attend:
        dsa.ATTEND_TILE, dsa.ATTEND_ROWS, *more = (
            int(x) for x in text.split(","))
        dsa.ATTEND_UNROLL = more[0] if more else 1
        plan = dsa.attend_plan(block, per_tier * block, dn, dv)
        out["kernels"][text] = dict(plan, **(
            reading(plan["attend_tile"])
            if plan["attend_form"] == "kernel" else {}))
        print(json.dumps({text: out["kernels"][text]}), flush=True)
    return out


def sweep_scores(a, block: int) -> dict:
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import dsa

    f32, bf16 = jnp.float32, jnp.bfloat16
    s, J, d = a.seq, a.heads, a.dim
    block, tiers = dsa.walk_plan(s, block, a.tiers)
    per_tier = s // block // tiers
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (s, J, d), f32)
    k = jax.random.normal(keys[1], (s, d), f32)
    w = jax.random.normal(keys[2], (s, J), f32) * (J * d) ** -0.5
    # a cotangent that is zero off a planted choice: ``topk`` keys a query
    g = jnp.where(jax.random.uniform(keys[3], (block, s)) < a.topk / s,
                  jax.random.normal(keys[3], (block, s), f32), 0.0)

    def small(x):
        """A few numbers of ``x`` that no fusion can reach past."""
        return jax.lax.optimization_barrier(x)[:1].astype(f32).sum()

    def walks(scores):
        """Jitted: one layer's walk of ``scores(q_b, k_t, w_b, first)``
        forward, and of its three gradients under ``g``; new functions
        every time, so that jit traces them anew."""
        def over_blocks(body, q, k, w, *more):
            def by_block(x):
                return x.reshape((tiers, per_tier, block) + x.shape[1:])

            firsts = (jnp.arange(s // block, dtype=jnp.int32) * block
                      ).reshape(tiers, per_tier)
            total = 0.0
            for t in range(tiers):
                end = (t + 1) * per_tier * block
                total = total + jax.lax.map(
                    lambda x, end=end: body(k[:end], end, *x, *more),
                    (by_block(q)[t], by_block(w)[t], firsts[t])).sum()
            return total

        def forward(q, k, w):
            return over_blocks(
                lambda k_t, end, q_b, w_b, first:
                small(scores(q_b, k_t, w_b, first)), q, k, w)

        def backward(q, k, w, g):
            def body(k_t, end, q_b, w_b, first, g):
                _, vjp = jax.vjp(
                    lambda *x: scores(*x, first), q_b, k_t, w_b)
                return sum(small(x) for x in vjp(g[:, :end]))

            return over_blocks(body, q, k, w, g)

        return jax.jit(forward), jax.jit(backward)

    def ms(fn, *xs):
        return _ms(fn, xs, a.calls)

    # one block against every key, the last block of the sequence
    rows = slice(s - block, s)
    cell = (q[rows].astype(bf16), k.astype(bf16), w[rows])
    exact = tuple(x.astype(f32) for x in cell)

    def one_block(scores):
        def outputs(q_b, k, w_b, g):
            out, vjp = jax.vjp(lambda *x: scores(*x, None), q_b, k, w_b)
            return (out,) + vjp(g)

        return jax.jit(outputs)

    def xla(q_b, k_t, w_b, first):
        return dsa.plain_scores(q_b, k_t, w_b)

    want = None
    if not a.no_gaps:
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(one_block(xla)(*exact, g))

    def reading(scores, every_tile=None):
        forward, backward = walks(scores)
        half = (q.astype(bf16), k.astype(bf16), w)
        out = {}
        try:
            out["forward_ms"] = ms(forward, *half)
            if every_tile is not None:
                out["forward_all_tiles_ms"] = ms(walks(every_tile)[0], *half)
            out["backward_ms"] = ms(backward, *half, g)
            out["step_ms"] = a.layers * (3 * out["forward_ms"]
                                         + out["backward_ms"])
            if want is not None:
                got = one_block(scores)(*cell, g)
                out["gap_to_float32"] = {
                    n: _gap(x, y) for n, x, y in zip(
                        ("scores", "dq_i", "dk_i", "dw"), got, want)}
        except Exception as e:  # noqa: BLE001 (a setting Mosaic refuses)
            out["error"] = f"{type(e).__name__}: {str(e)[:400]}"
        return out

    out = {"device": jax.devices()[0].device_kind, "seq": s, "heads": J,
           "dim": d, "block": block, "tiers": tiers, "calls": a.calls,
           "pairs_scored": sum(per_tier * block * (t + 1) * per_tier * block
                               for t in range(tiers)),
           "xla": {} if a.no_xla else reading(xla), "kernels": {}}
    print(json.dumps({"xla": out["xla"]}), flush=True)
    names = ("SCORE_TILE", "SCORE_ROWS")
    settings = [tuple(int(x) for x in text.split(",")) for text in a.kernels
                ] or [tuple(getattr(dsa, n) for n in names)]
    for setting in settings:
        for name, value in zip(names, setting):
            setattr(dsa, name, value)
        key = ",".join(str(v) for v in setting)
        plan = dsa.scores_plan(block, per_tier * block, J, d)
        out["kernels"][key] = dict(plan, **(reading(
            dsa._scores, every_tile=lambda q_b, k_t, w_b, first:
            dsa.index_scores(q_b, k_t, w_b))
            if plan["scores_form"] == "kernel" else {}))
        print(json.dumps({key: out["kernels"][key]}), flush=True)
    return out


if __name__ == "__main__":
    main()
