"""A chunked recurrence alone on the chip, one process: forward and
gradient of ``ops/delta.gated_delta_rule`` (``--op rule``, the default) or
of ``ops/ssm.ssd_scan`` (``--op scan``) at a cell's shapes, milliseconds a
layer, for XLA's walk and for the kernels over their constants, and how far
each form's outputs and gradients lie from a float32 reference.

- ``rule``: ``train-olmo-hybrid-1chip``'s shapes by default (1 x 32,768
  positions, 30 heads, keys of 96, values of 192, chunk 64, bfloat16);
  ``--key-heads`` gives q and k fewer heads than ``--heads``, the value
  heads' (``train-qwen3-next-1chip``: ``--heads 32 --key-heads 16 --key-dim
  128 --value-dim 128``); ``--kernels heads,chunks,base`` sets
  ``KERNEL_HEADS``, ``KERNEL_CHUNKS`` and ``KERNEL_BASE``; the reference is
  XLA's walk on float32 operands at the highest matmul precision. Beside
  ``gated_delta_rule`` (operands [b, s, heads, dim], swapped around the
  kernels) each setting of the kernels reads ``around``: the two Mosaic
  calls alone on operands that lie as they take them (``calls``) and what
  the mixer runs under ``gdn_rule`` whole, from the taps' outputs to the
  gated norm's input (``part``: ``ops/delta.rule_part``), so that what XLA
  does around the kernels is a number.
- ``scan``: ``train-granite-1chip``'s (1 x 32,768 positions, 64 heads of
  64, a state of 128, one group, chunk 256, bfloat16); ``--kernels
  heads,chunks`` sets ``KERNEL_HEADS`` and ``KERNEL_CHUNKS``; the reference
  is the recurrence token by token in float32 (its gradient by a scan of
  chunks under ``jax.checkpoint``).

    python3 ray_tpu/tools/rule_sweep.py [--op scan] [--seq 32768] \
        [--kernels 2,8,16 ...]

``--kernels`` may be given again; without it the module's constants are
read alone; ``--no-gaps`` reads the times alone. Prints one JSON object and
writes it to ``chiprun_out/<--out>`` (``<op>_sweep.json``). Run as a file; a
time read on the CPU is no device number (the kernels then run in the
Pallas interpreter: use a short ``--seq``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# what each op's shapes default to (the cell that runs it)
SHAPES = {"rule": dict(heads=30, key_dim=96, value_dim=192, chunk=64),
          "scan": dict(heads=64, head_dim=64, state=128, chunk=256)}


def _rule(a):
    """The gated delta rule: the module, its kernels' name, the constants
    ``--kernels`` sets, float32 inputs (the cotangent last; ``half``: which
    the cell holds in bfloat16), their names, ``call(*inputs, mesh)``,
    ``plan(mesh)``, ``laid`` (inputs and outputs as a form takes them) and
    the reference (none: XLA's walk in float32)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import delta
    from ray_tpu.ops.layers import l2_norm

    f32 = jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    shape = (1, a.seq, a.heads)
    key_heads = a.key_heads or a.heads
    q, k = (l2_norm(jax.random.normal(
        key, (1, a.seq, key_heads, a.key_dim), f32), scale=scale)
            for key, scale in ((keys[0], a.key_dim ** -0.5), (keys[1], 1.0)))
    v = jax.random.normal(keys[2], shape + (a.value_dim,), f32)
    g = -jax.nn.softplus(jax.random.normal(keys[3], shape) - 2.0)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], shape))
    do = jax.random.normal(keys[5], v.shape, f32)

    def call(*xs, mesh):
        return delta.gated_delta_rule(*xs, chunk=a.chunk, mesh=mesh)

    def plan(mesh):
        return delta.rule_plan(1, a.seq, a.heads, a.key_dim, a.value_dim,
                               a.chunk, mesh, key_heads)

    def around(cell):
        """{reading: (function, its inputs with the cotangent last)}: the
        two calls alone, and the mixer's ``gdn_rule`` part whole (the
        cell's q, k, v stand for the taps' outputs, its g and beta for the
        in-projection's a and b)."""
        q, k, v, g, beta, do = cell
        dims, now = (a.key_dim, a.value_dim), plan(None)
        whole = now["steps"] * now["chunks_a_call"] * now["chunk"]
        norm = (delta.QK_NORM_EPS, a.key_dim ** -0.5)

        def last(x, to=a.seq):
            return jnp.pad(jnp.swapaxes(x.reshape(1, a.seq, -1), 1, 2),
                           ((0, 0), (0, 0), (0, to - a.seq)))

        p = {"g_A_log": jnp.zeros((a.heads,), f32),
             "g_dt_bias": jnp.zeros((a.heads,), f32)}

        def calls(*xs):
            return delta._rule_calls(*xs, dims, norm, on_cpu)

        def part(*xs):
            return delta.rule_part(
                *xs, p, heads=a.heads, key_heads=key_heads,
                key_dim=a.key_dim, value_dim=a.value_dim, chunk=a.chunk)

        return {"calls": (calls, (last(q, whole), last(k, whole),
                                  last(v, whole),
                                  *delta._sums_laid(g, beta, now),
                                  last(do, whole))),
                "part": (part, (last(q), last(k), last(v),
                                g.astype(q.dtype), beta.astype(q.dtype),
                                do))}

    on_cpu = jax.default_backend() == "cpu"
    return dict(
        module=delta, kernels="rule_kernels",
        constants=("KERNEL_HEADS", "KERNEL_CHUNKS", "KERNEL_BASE"),
        inputs=(q, k, v, g, beta, do), half=(0, 1, 2, 5),
        names=("q", "k", "v", "g", "beta"), call=call, plan=plan,
        laid=lambda xs, form: xs, reference=None, around=around,
        gap_to="gap_to_float32_walk")


def _scan(a):
    """The selective scan, as ``_rule`` gives the rule."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssm

    f32 = jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    H, P, N = a.heads, a.head_dim, a.state
    x = jax.random.normal(keys[0], (1, a.seq, H, P), f32)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (1, a.seq, H)) - 2.0)
    A = -jnp.exp(jax.random.normal(keys[2], (H,)))
    B, C = (jax.random.normal(key, (1, a.seq, 1, N), f32) * N ** -0.25
            for key in keys[3:5])
    do = jax.random.normal(keys[5], x.shape, f32)

    def plan(mesh):
        return ssm.scan_plan(1, a.seq, H, P, N, 1, a.chunk, mesh)

    def call(*xs, mesh):
        """Each form on operands that lie as it takes them (the kernels
        positions last, as the taps' kernels leave them in the mixer; the
        relayouts are outside what is timed)."""
        if plan(mesh)["form"] != "pallas":
            return ssm.ssd_scan(*xs, chunk=a.chunk, mesh=mesh)
        x, dt, A_, B_, C_ = xs
        return ssm.scan_kernels(x, dt, A_, B_, C_, plan(mesh))

    def laid(xs, form):
        """Arrays [1, s, ...] as ``form`` takes and gives them: [1, ..., s]
        for the kernels."""
        if form != "pallas":
            return xs
        return tuple(x if x.ndim == 1 else jnp.swapaxes(
            x.reshape(1, a.seq, -1), 1, 2) for x in xs)

    def recurrence(x, dt, A, B, C):
        """Token by token, float32; chunks of positions under
        ``jax.checkpoint``, so that a gradient keeps one state a chunk."""
        def token(S, t):
            x_t, dt_t, B_t, C_t = t         # [H, P], [H], [N], [N]
            S = (jnp.exp(dt_t * A)[:, None, None] * S
                 + (dt_t[:, None] * x_t)[..., None] * B_t)
            return S, (S * C_t).sum(-1)

        @jax.checkpoint
        def chunk(S, ts):
            return jax.lax.scan(token, S, ts)

        n = -(-a.seq // a.chunk)
        ts = tuple(jnp.pad(t[0], ((0, n * a.chunk - a.seq),)
                           + ((0, 0),) * (t.ndim - 2)
                           ).reshape((n, a.chunk) + t.shape[2:])
                   for t in (x, dt, B[:, :, 0], C[:, :, 0]))
        S, y = jax.lax.scan(chunk, jnp.zeros((H, P, N), f32), ts)
        return y.reshape((1, -1) + y.shape[2:])[:, :a.seq], S[None]

    return dict(
        module=ssm, kernels="scan_kernels",
        constants=("KERNEL_HEADS", "KERNEL_CHUNKS"),
        inputs=(x, dt, A, B, C, do), half=(0, 3, 4, 5),
        names=("x", "dt", "A", "B", "C"), call=call, plan=plan, laid=laid,
        reference=recurrence, gap_to="gap_to_float32_recurrence")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--op", choices=sorted(SHAPES), default="rule")
    ap.add_argument("--seq", type=int, default=32768)
    for name in sorted({n for s in SHAPES.values() for n in s}):
        ap.add_argument("--" + name.replace("_", "-"), type=int)
    ap.add_argument("--key-heads", type=int)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--kernels", action="append", default=[])
    ap.add_argument("--no-gaps", action="store_true")
    ap.add_argument("--out")
    a = ap.parse_args()
    for name, value in SHAPES[a.op].items():
        if getattr(a, name) is None:
            setattr(a, name, value)
    import functools

    import jax
    import jax.numpy as jnp

    op = {"rule": _rule, "scan": _scan}[a.op](a)
    module, call = op["module"], op["call"]
    on_cpu = jax.default_backend() == "cpu"
    f32, bf16 = jnp.float32, jnp.bfloat16
    exact = op["inputs"]
    cell = tuple(x.astype(bf16) if i in op["half"] else x
                 for i, x in enumerate(exact))
    # any mesh keeps XLA's walk
    walk = jax.sharding.Mesh(jax.devices()[:1], ("x",))

    def programs(fn, inputs=len(exact) - 1):
        def loss(*xs):
            o, _ = fn(*xs[:-1])
            return (o.astype(f32) * xs[-1]).sum()

        return jax.jit(fn), jax.jit(jax.grad(
            loss, argnums=tuple(range(inputs))))

    def ms(fn, *xs):
        jax.block_until_ready(fn(*xs))
        jax.block_until_ready(fn(*xs))
        t = time.perf_counter()
        for _ in range(a.calls):
            out = fn(*xs)
        jax.block_until_ready(out)
        return (time.perf_counter() - t) / a.calls * 1e3

    def gap(got, want):
        got, want = got.astype(f32), want.astype(f32)
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    want = None
    if not a.no_gaps:
        with jax.default_matmul_precision("highest"):
            fn, grad = programs(op["reference"] or functools.partial(
                call, mesh=walk))
            want = jax.block_until_ready((fn(*exact[:-1]), grad(*exact)))

    def reading(mesh):
        fn, grad = programs(functools.partial(call, mesh=mesh))
        plan = op["plan"](mesh)
        out = {"plan": {n: plan.get(n) for n in (
            "form", "joined", "steps", "heads_a_block", "chunks_a_call",
            "states_kept", "float32_bytes_in_hbm")}}
        form = plan["form"]
        xs = op["laid"](cell, form)
        try:
            out["forward_ms"] = ms(fn, *xs[:-1])
            out["gradient_ms"] = ms(grad, *xs)
            if form == "pallas" and "around" in op:
                out["around"] = {}
                for name, (part, ys) in op["around"](cell).items():
                    part_fn, part_grad = programs(part, len(ys) - 1)
                    out["around"][name] = {
                        "forward_ms": ms(part_fn, *ys[:-1]),
                        "gradient_ms": ms(part_grad, *ys)}
            if want is not None:
                (o, S), grads = fn(*xs[:-1]), grad(*xs)
                want_o, *want_grads = op["laid"]((want[0][0],) + want[1],
                                                 form)
                out[op["gap_to"]] = dict(
                    o=gap(o, want_o), state=gap(S, want[0][1]),
                    **{"d" + n: gap(x, w) for n, x, w in zip(
                        op["names"], grads, want_grads)})
        except Exception as e:  # noqa: BLE001 (a setting Mosaic refuses)
            out["error"] = f"{type(e).__name__}: {str(e)[:400]}"
        return out

    out = dict({"device": jax.devices()[0].device_kind, "op": a.op,
                "seq": a.seq, "key_heads": a.key_heads},
               **{n: getattr(a, n) for n in SHAPES[a.op]},
               xla_walk=reading(walk), kernels={})
    print(json.dumps({"xla_walk": out["xla_walk"]}), flush=True)
    if on_cpu:
        setattr(module, op["kernels"], functools.partial(
            getattr(module, op["kernels"]), interpret=True))
        jax.default_backend = lambda: "tpu"
    names = op["constants"]
    settings = [tuple(int(x) for x in s.split(",")) for s in a.kernels] or [
        tuple(getattr(module, n) for n in names)]
    for setting in settings:
        for name, value in zip(names, setting):
            setattr(module, name, value)
        key = ",".join(str(v) for v in setting)
        out["kernels"][key] = reading(None)
        print(json.dumps({key: out["kernels"][key]}), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           a.out or f"{a.op}_sweep.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
