"""XLA's scatter-add of rows alone on the chip, one process: what
``table.at[idx].add(rows)`` costs a row over the table's width, the rows
added, the table's rows and the order of the indices, and what the held
experts' passes (``ops/moe.routed_experts(held=)``), which add their rows
that way, cost over the width of a block of their sums.

- ``scatter``: float32 ``[table_rows, columns]``, ``rows`` update rows,
  indices ``pass`` (as a pass of the held rows has them: eight experts'
  groups one after another, a group's tokens ascending, a token at most
  once a group), ``by_token`` (the same sorted) and ``unique`` (no token
  twice, any order; where the table has the rows for it). The table is
  donated and handed on from call to call, as a loop's carry is.
- ``blocked``: the same rows added to ``columns / width`` tables of
  ``width`` columns each, the rows sliced by columns (``layers._add_rows``).
- ``onehot``: the rows added by a product with their 0/1 token matrix
  (``[table_rows, rows] x [rows, columns]``, bfloat16 operands, float32
  sums): the form for a thin share if the width were no cliff.
- ``embedding``: a token gather's gradient (``ops/layers.embed_rows``, the
  models' ``embed``): the cotangent's rows of ``--embed-tokens`` random
  tokens (repeats among them) added into a bfloat16 ``[--embed-table-rows,
  --embed-columns]`` table, ``whole`` (jax's transpose: one scatter-add of
  all columns) beside ``blocked`` (the function's own transpose at the
  block ``_sum_columns`` would give that width past ``_SUM_WHOLE``, its
  join and its cast with it), the sums held in bfloat16 and in float32.
- ``op``: ``routed_experts(held=(0, count))`` forward and gradient at
  ``train-deepseek-v2-1chip``'s shape (8,192 tokens of 5,120, 8 of 160
  experts of 1,536, 6 a token of 3 of 8 groups), milliseconds a layer for
  the forward and for the gradient's program (the router and the backward's
  passes: a pass's residuals are its inputs, so it holds no forward pass) at
  each ``--sum-columns`` (``layers._SUM_WHOLE`` and ``._SUM_COLUMNS`` both
  set to it: one sum up to that width, past it the largest divisor under).

    python3 ray_tpu/tools/scatter_sweep.py [--columns 2048,5120] \
        [--rows 3072] [--table-rows 8192] [--sum-columns 8192,2560] \
        [--embed-columns 5120] [--embed-table-rows 12800,19008] \
        [--embed-tokens 8192,16384] [--skip scatter,blocked,onehot,op]

Every array is an argument of the jitted call (a closed-over one compiles
into the executable: PERF.md 5, PR 35); a time is the wall clock around
``block_until_ready``, the median of ``--calls`` after two warm calls.
Prints a line a reading and writes all of them to ``chiprun_out/<--out>``
(``scatter_sweep.json``). Run as a file; a time read on the CPU is no device
number.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

GROUPS = 8      # the experts whose groups a pass's indices run through


def _ints(text: str):
    return [int(x) for x in text.split(",") if x]


def _indices(rng, order: str, rows: int, table_rows: int):
    """``rows`` token ids under ``table_rows`` in ``order``; None where
    the table has not the rows for it."""
    import numpy as np

    if order == "unique":
        if rows > table_rows:
            return None
        return rng.permutation(table_rows)[:rows].astype(np.int32)
    if order == "random":
        return rng.integers(0, table_rows, rows).astype(np.int32)
    each = rows // GROUPS
    if each > table_rows:
        return None
    idx = np.concatenate([np.sort(rng.permutation(table_rows)[:each])
                          for _ in range(GROUPS)]).astype(np.int32)
    return np.sort(idx) if order == "by_token" else idx


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--columns", type=_ints,
                    default=[2048, 2560, 3072, 4096, 5120, 6144, 8192])
    ap.add_argument("--rows", type=_ints, default=[3072, 11520, 36864])
    ap.add_argument("--table-rows", type=_ints, default=[8192, 16384])
    ap.add_argument("--orders", default="pass,by_token,unique")
    ap.add_argument("--blocked-columns", type=_ints,
                    default=[5120, 6144, 8192],
                    help="widths whose sums are also taken in blocks")
    ap.add_argument("--sum-columns", type=_ints,
                    default=[8192, 4096, 2048, 1024, 512],
                    help="widths of a block of the sums the op is read under")
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--hidden", type=int, default=5120)
    ap.add_argument("--experts", type=int, default=160)
    ap.add_argument("--held", type=int, default=8)
    ap.add_argument("--width", type=int, default=1536)
    ap.add_argument("--top-k", type=int, default=6)
    ap.add_argument("--groups", type=_ints, default=[8, 3])
    ap.add_argument("--embed-columns", type=_ints,
                    default=[2560, 3840, 4096, 5120, 6144, 7680])
    ap.add_argument("--embed-table-rows", type=_ints, default=[12800, 19008])
    ap.add_argument("--embed-tokens", type=_ints, default=[8192, 16384])
    ap.add_argument("--calls", type=int, default=7)
    ap.add_argument("--skip", default="",
                    help="parts left out: scatter,blocked,onehot,embedding,op")
    ap.add_argument("--out", default="scatter_sweep.json")
    a = ap.parse_args()
    skip = set(a.skip.split(","))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import layers, moe

    f32, bf16 = jnp.float32, jnp.bfloat16
    rng = np.random.default_rng(0)
    out = {"device": jax.devices()[0].device_kind, "calls": a.calls,
           "scatter": [], "blocked": [], "onehot": [], "embedding": [],
           "op": []}

    def ms(fn, carry, *xs):
        """Median milliseconds of ``carry = fn(carry, *xs)``, the carry
        donated; of ``fn(*xs)`` where ``carry`` is None."""
        times = []
        for _ in range(a.calls + 2):
            t = time.perf_counter()
            if carry is None:
                jax.block_until_ready(fn(*xs))
            else:
                carry = jax.block_until_ready(fn(carry, *xs))
            times.append((time.perf_counter() - t) * 1e3)
        return statistics.median(times[2:])

    def say(part, **reading):
        out[part].append(reading)
        print(json.dumps({part: reading}), flush=True)

    def updates(rows, columns, dtype=f32):
        # bfloat16 values, as a pass's rows are before their float32 sums
        return jax.random.normal(jax.random.PRNGKey(rows + columns),
                                 (rows, columns), bf16).astype(dtype)

    add = jax.jit(lambda table, idx, rows: table.at[idx].add(rows),
                  donate_argnums=0)
    out["floor_ms"] = ms(jax.jit(lambda t: t + 1.0, donate_argnums=0),
                         jnp.zeros((8, 128), f32))
    print(json.dumps({"floor_ms": out["floor_ms"]}), flush=True)

    if "scatter" not in skip:
        for columns in a.columns:
            for rows in a.rows:
                y = updates(rows, columns)
                for table_rows in a.table_rows:
                    for order in a.orders.split(","):
                        idx = _indices(rng, order, rows, table_rows)
                        if idx is None:
                            continue
                        t = ms(add, jnp.zeros((table_rows, columns), f32),
                               jnp.asarray(idx), y)
                        say("scatter", columns=columns, rows=rows,
                            table_rows=table_rows, order=order, ms=t,
                            us_a_row=t * 1e3 / rows)

    blocked = jax.jit(layers._add_rows, donate_argnums=0)
    if "blocked" not in skip:
        for columns in a.blocked_columns:
            for rows in a.rows[:2]:
                y = updates(rows, columns)
                idx = jnp.asarray(_indices(rng, "pass", rows, a.tokens))
                widths = [w for w in range(128, columns + 1, 128)
                          if columns % w == 0 and w >= 512]
                for width in widths:
                    sums = tuple(jnp.zeros((a.tokens, width), f32)
                                 for _ in range(columns // width))
                    t = ms(blocked, sums, idx, y)
                    say("blocked", columns=columns, rows=rows,
                        table_rows=a.tokens, width=width,
                        blocks=columns // width, ms=t,
                        us_a_row=t * 1e3 / rows)

    @jax.jit
    def onehot(table, idx, rows):
        hot = (jnp.arange(table.shape[0])[:, None] == idx[None, :])
        return table + jnp.dot(hot.astype(bf16), rows.astype(bf16),
                               preferred_element_type=f32)

    if "onehot" not in skip:
        for columns in a.blocked_columns[:1]:
            for rows in a.rows[:2]:
                t = ms(onehot, jnp.zeros((a.tokens, columns), f32),
                       jnp.asarray(_indices(rng, "pass", rows, a.tokens)),
                       updates(rows, columns))
                say("onehot", columns=columns, rows=rows,
                    table_rows=a.tokens, ms=t, us_a_row=t * 1e3 / rows)

    if "embedding" not in skip:
        def gradient(dtype):    # a new function: jit traces it anew
            def d_table(table, tokens, ct):
                return jax.vjp(lambda t: layers.embed_rows(t, tokens, dtype),
                               table)[1](ct)[0]
            return jax.jit(d_table)

        rule = layers._SUM_WHOLE
        for columns in a.embed_columns:
            for table_rows in a.embed_table_rows:
                table = jnp.zeros((table_rows, columns), bf16)
                for rows in a.embed_tokens:
                    tokens = jnp.asarray(_indices(rng, "random", rows,
                                                  table_rows))
                    for dtype in (bf16, f32):
                        ct = updates(rows, columns, dtype)
                        # no width is past its own, every width is past 0
                        for whole in (columns, 0):
                            layers._SUM_WHOLE = whole
                            plan = layers.embed_plan(rows, table_rows,
                                                     columns)
                            t = ms(gradient(dtype), None, table, tokens, ct)
                            say("embedding", columns=columns, rows=rows,
                                table_rows=table_rows,
                                sums=jnp.dtype(dtype).name,
                                form=plan["form"],
                                sum_columns=plan["sum_columns"], ms=t,
                                us_a_row=t * 1e3 / rows)
        layers._SUM_WHOLE = rule

    if "op" not in skip:
        n, h, E, f = a.tokens, a.hidden, a.experts, a.width
        keys = jax.random.split(jax.random.PRNGKey(0), 6)
        x, cot = (jax.random.normal(k, (n, h), f32).astype(bf16)
                  for k in keys[:2])
        router = jax.random.normal(keys[2], (h, E), f32) * h ** -0.5
        e_gate, e_up = (
            (jax.random.normal(k, (a.held, h, f), f32) * h ** -0.5
             ).astype(bf16) for k in keys[3:5])
        e_down = (jax.random.normal(keys[5], (a.held, f, h), f32)
                  * f ** -0.5).astype(bf16)
        args = (x, router, e_gate, e_up, e_down)
        groups = tuple(a.groups) if a.groups else None

        for limit in a.sum_columns:
            layers._SUM_WHOLE = layers._SUM_COLUMNS = limit

            def layer(*xs):     # a new function: jit traces it anew
                return moe.routed_experts(*xs, a.top_k, held=(0, a.held),
                                          scale=16.0, groups=groups)

            forward = jax.jit(layer)
            gradient = jax.jit(jax.grad(
                lambda *xs: (layer(*xs[:-1])[0].astype(f32)
                             * xs[-1].astype(f32)).sum(),
                argnums=(0, 1, 2, 3, 4)))
            held_rows = int(forward(*args)[2][:a.held].sum())
            say("op", sum_columns=limit, block=layers._sum_columns(h),
                blocks=h // layers._sum_columns(h), held_rows=held_rows,
                chunk=moe._held_chunk(n * a.top_k, a.held, E),
                forward_ms=ms(forward, None, *args),
                gradient_ms=ms(gradient, None, *args, cot))

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", a.out)
    with open(path, "w") as file:
        json.dump(out, file, indent=1)
    print("wrote", path)


if __name__ == "__main__":
    main()
