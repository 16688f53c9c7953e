"""The compiled train step of each benchmark cell, as text: what a PR that
moves model code without meaning to change a program compares, parent
against change, before any chip does.

For every training cell of ``BENCHMARK.json`` the cell's adamw step
(``benchmark/cells/train.py``, ``train_moe.py``, ``train_mixed.py`` and
``train_hybrid.py``, ``train_scan.py`` and ``train_delta.py``, whose own
``make_step`` is compiled: the cell's
configuration, batch, mesh and donation) is compiled for a v5e host that
is described and not attached, with ``jax.default_backend`` answering
"tpu" and ``llama._device_capacity`` a v5e chip's limit, as
``tests/test_tpu_compile.py`` does. Nothing runs: no time and no result
comes from here. Three 7B-width compiles take minutes, so this is a
script and not a tier-1 test.

    python ray_tpu/tools/step_program.py --tree <checkout> --out <dir> [--cell <name>]
    python ray_tpu/tools/step_program.py --compare <dir-a> <dir-b>

Run as a file and not with ``-m``: ``--tree`` (default: this checkout) is
the checkout whose ``ray_tpu`` and ``benchmark/`` are read, so the same
script judges a parent commit unpacked elsewhere. ``--out`` gets
``<cell>.hlo.txt`` (``as_text()`` without metadata) and ``summary.json``
(its sha256, ``memory_analysis()``, the resolved remat level, by kind for
a stack of kinds, the count of Mosaic calls and their names; and, about
the program and not of it, ``remat``: the whole ``rtpu.train.remat_plan``
span beside what the compiler allotted, arguments + temporaries + outputs
- aliased, which is what the plan's ``need_bytes`` is held against;
``flash_tiles``: what each distinct flash kernel call of the step visits,
from its ``rtpu.flash.tiles`` span, ``scan_plan``: the same of each
distinct selective scan, from its ``rtpu.ssm.scan_plan`` span,
``conv_plan``: of the taps before it, from ``rtpu.ssm.conv_plan``,
``rule_plan`` and ``gdn_conv_plan``: the same of each distinct gated delta
rule (its ``form``; how a value head comes by its key head, ``joined``:
the kernels' "index_map" or the walk's "repeat"; how the kernels'
``operands`` lie: "positions_last", as the taps leave them) and of its
taps, from ``rtpu.gdn.rule_plan`` and ``rtpu.gdn.conv_plan``, ``embed_plan``: the token gather's and the form
of its gradient, from ``rtpu.embed.plan``, ``latent_plan`` and ``mtp_plan``:
a mixture in a latent's and a prediction module's, from
``rtpu.moe.latent_plan`` and ``rtpu.train.mtp_plan``, ``scopes``: how many
instructions carry each ``jax.named_scope`` name as the innermost, and
``mosaic_fused``: the Mosaic calls XLA folded into a fusion with a
consumer, by name (a device trace then shows ``fusion.N`` under the
consumer's scope and no such call: dots3's second ``dsa_attend_fwd`` a
layer, PR 58), and
``trace_s``, ``lower_s``, ``compile_s`` and ``mosaic_lowerings``: the
seconds this machine took to trace the step and to lower it, and how often
each Mosaic call's body was lowered, by the call's name: what a change
does to a cell's ``setup_trace_lower_s``, read here before a chip reads
it; the bench host takes about 2.5 times the seconds, PR 58).
A Mosaic call's body is compared without its source lines (a location is
left unknown), so both directories of a ``--compare`` come from this script.
``--compare`` judges the program (``PROGRAM_FIELDS``) and says of two
differing programs how many lines changed and how many of those are calls
of the flash kernels.
"""

from __future__ import annotations

import argparse
import difflib
import hashlib
import json
import os
import re
import sys
import time

V5E_BYTES_LIMIT = int(15.75 * 2 ** 30)
MEMORY_FIELDS = ("argument_size_in_bytes", "output_size_in_bytes",
                 "alias_size_in_bytes", "temp_size_in_bytes",
                 "generated_code_size_in_bytes", "peak_memory_in_bytes")
# what --compare holds two programs equal by; the rest of a summary
# describes the program and may gain keys
PROGRAM_FIELDS = ("sha256", "lines", "mosaic_calls", "remat_plan",
                  "memory_analysis")
OP_NAME = re.compile(r'op_name="([^"]*)"')
SCOPE = re.compile(r"(?<![A-Za-z0-9_.])(?<!jit\()([a-z][a-z0-9]*(?:_[a-z0-9]+)+|embed|"
                   r"flash|mlp)(?=[/)])")
JAX_OWN = ("closed_call", "rematted_computation", "custom_vjp_call",
           "custom_jvp_call", "pallas_call", "shard_map")
SERIAL = re.compile(r"\b([A-Za-z_][\w-]*)\.\d+\b")
FLASH_CALL = re.compile(
    r'%flash_\w+ = .*custom_call_target="tpu_custom_call"')


def strip_metadata(text: str) -> str:
    """``compiled.as_text()`` without what names source and scopes: each
    instruction's metadata, and the module's tables of source files and
    stack frames that the metadata points into."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return re.sub(r"\nFileNames\n.*?\nStackFrames\n.*?\n\n", "\n",
                  text, flags=re.S)


def _compile_cell(tree: str, cell: dict, topo) -> dict:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from importlib import import_module
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.sharding import batch_sharding
    from ray_tpu.util import tracing

    def load(kind, name):
        with open(os.path.join(tree, "benchmark", kind, name + ".json")) as f:
            return json.load(f)

    tr = load("traffic", cell["traffic"])
    kw = {k: tuple(v) if isinstance(v, list) else v for k, v in
          load("configs", cell["config"])["model_config"].items()}
    preset = kw.pop("preset")
    for key in ("dtype", "param_dtype"):
        kw[key] = getattr(jnp, kw[key])
    moe = tr["family"] != "train"      # the step returns its expert counts
    name = kw.pop("module", "olmoe" if moe else "llama")
    mod = import_module("ray_tpu.models." + name)
    cfg = getattr(getattr(mod, name.capitalize() + "Config"), preset)(
        **kw, attn_impl="auto")
    devs = topo.devices[:cell["chips"]]
    mesh = None
    rep = psh = bsh = SingleDeviceSharding(devs[0])
    if tr["mesh_axes"]:
        mesh = build_mesh(MeshSpec(tr["mesh_axes"]), devices=devs)
        psh = mod.param_shardings(cfg, mesh)
        bsh, rep = batch_sharding(mesh), NamedSharding(mesh, P())

    def placed(tree_, sh):
        if not isinstance(sh, (dict, tuple, list)):
            sh = jax.tree_util.tree_map(lambda _: sh, tree_)
        return jax.tree_util.tree_map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), tree_, sh)

    lr = tr["lr"]
    if tr.get("lr_warmup_steps"):     # as the cell's runner builds it
        lr = optax.linear_schedule(0.0, lr, tr["lr_warmup_steps"])
    tx = optax.adamw(lr)
    shapes = jax.eval_shape(lambda k: mod.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    params = placed(shapes, psh)
    # the optimizer's leaves (all, but where the module says otherwise)
    owned = getattr(mod, "trainable", lambda p: p)
    # the moments lie where the parameters do; the step count is one scalar
    opt = jax.eval_shape(tx.init, owned(shapes))
    opt = (opt[0]._replace(count=placed(opt[0].count, rep), mu=owned(params),
                           nu=owned(params)),) + placed(tuple(opt[1:]), rep)
    batch = {"tokens": jax.ShapeDtypeStruct(
        # a row's ids past ``seq``: the targets (two with a prediction
        # module, the traffic's ``ids_ahead``)
        (tr["batch"], tr["seq"] + tr.get("ids_ahead", 1)), jnp.int32,
        sharding=bsh)}

    runner = import_module("benchmark.cells." + tr["family"])
    if hasattr(runner, "batch_shapes"):   # a batch of more than its ids
        batch = {name: jax.ShapeDtypeStruct(
            shape, getattr(jnp, dtype), sharding=bsh)
            for name, (shape, dtype) in runner.batch_shapes(tr).items()}
    if hasattr(runner, "make_step"):    # the cell's own step, as it stands
        step = runner.make_step(mod, cfg, tx, mesh)
    elif moe:
        def step(params, opt, batch):
            (loss, aux), grads = jax.value_and_grad(
                lambda p: mod.loss_terms(cfg, p, batch, mesh=mesh),
                has_aux=True)(params)
            updates, opt = tx.update(grads, opt, params)
            return (optax.apply_updates(params, updates), opt, loss,
                    aux["expert_counts"])
    else:
        def step(params, opt, batch):
            loss, grads = jax.value_and_grad(
                lambda p: mod.loss_fn(cfg, p, batch, mesh=mesh))(params)
            updates, opt = tx.update(grads, opt, params)
            return optax.apply_updates(params, updates), opt, loss

    here = tracing.since()
    lowerings, clock = {}, [time.perf_counter()]

    def counted(ctx, *nodes, name=None, **params):
        lowerings[str(name)] = lowerings.get(str(name), 0) + 1
        return lower_call(ctx, *nodes, name=name, **params)

    from jax._src.pallas.mosaic import pallas_call_registration as mosaic
    lower_call = mosaic.pallas_call_tpu_lowering_rule
    mosaic.pallas_call_tpu_lowering_rule = counted
    try:
        compiled = jax.jit(step, donate_argnums=(0, 1)).trace(
            params, opt, batch)
        for stage in ("lower", "compile"):
            clock.append(time.perf_counter())
            compiled = getattr(compiled, stage)()
        clock.append(time.perf_counter())
    finally:
        mosaic.pallas_call_tpu_lowering_rule = lower_call
    trace_s, lower_s, compile_s = (
        round(b - a, 2) for a, b in zip(clock, clock[1:]))
    events = here.events()
    plans = [{k: v for k, v in e["args"].items()
              if k not in ("id", "parent", "self_us")} for e in events
             if e["name"] == "rtpu.train.remat_plan"]
    # one line a distinct kernel call of the step: what its loops visit
    def distinct(span):
        return [json.loads(t) for t in sorted({json.dumps(
            {k: v for k, v in e["args"].items()
             if k not in ("id", "parent", "self_us")})
            for e in events if e["name"] == span})]

    tiles = distinct("rtpu.flash.tiles")
    # and one a distinct selective scan: its chunks and how it walks them
    scans = distinct("rtpu.ssm.scan_plan")
    taps = distinct("rtpu.ssm.conv_plan")
    # and one a distinct gated delta rule, and its taps
    rules = distinct("rtpu.gdn.rule_plan")
    rule_taps = distinct("rtpu.gdn.conv_plan")
    # and the token gather: whether its gradient adds in column blocks
    embeds = distinct("rtpu.embed.plan")
    # and a mixture in a latent, and a prediction module beside the head
    latents = distinct("rtpu.moe.latent_plan")
    modules = distinct("rtpu.train.mtp_plan")
    full = compiled.as_text()
    scopes = {}
    for path in OP_NAME.findall(full):
        found = [n for n in SCOPE.findall(path) if n not in JAX_OWN]
        if found:
            scopes[found[-1]] = scopes.get(found[-1], 0) + 1
    kernels = sorted(set(re.findall(
        r'%([\w.-]+?)(?:\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"',
        full)))
    # the calls XLA folded into a fusion of its own, by the call's name
    fused, inside = {}, False
    for line in full.splitlines():
        if line[:1] not in (" ", "}", ""):
            inside = line.lstrip("%").startswith("fused_computation")
        elif inside and 'custom_call_target="tpu_custom_call"' in line:
            name = re.match(r"\s*(?:ROOT )?%([A-Za-z_]+)", line).group(1)
            fused[name] = fused.get(name, 0) + 1
    text = strip_metadata(full)
    ma = compiled.memory_analysis()
    # what the plan's need is held against (tests/test_tpu_compile.py)
    allotted = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    return {"text": text, "summary": {
        "sha256": hashlib.sha256(text.encode()).hexdigest(),
        "lines": text.count("\n"),
        "mosaic_calls": text.count("tpu_custom_call"),
        "remat_plan": plans[0]["level"] if plans else None,
        "remat": {**plans[0], "allotted_bytes": allotted,
                  "allotted_over_need": round(
                      allotted / plans[0]["need_bytes"], 4)}
        if plans else None,
        "mosaic_kernels": kernels,
        "mosaic_fused": dict(sorted(fused.items())),
        "flash_tiles": tiles,
        "scan_plan": scans,
        "conv_plan": taps,
        "rule_plan": rules,
        "gdn_conv_plan": rule_taps,
        "embed_plan": embeds,
        "latent_plan": latents,
        "mtp_plan": modules,
        "scopes": dict(sorted(scopes.items())),
        "trace_s": trace_s, "lower_s": lower_s, "compile_s": compile_s,
        "mosaic_lowerings": dict(sorted(lowerings.items())),
        "memory_analysis": {f: getattr(ma, f) for f in MEMORY_FIELDS}}}


def compile_cells(tree: str, out: str, only=()) -> None:
    sys.path.insert(0, tree)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    from ray_tpu.models import llama

    # a compile for a described device cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    # A Mosaic kernel's serialized body (the custom call's backend_config)
    # carries the location of every Python frame that led to it, and
    # strip_metadata cannot reach inside: with the callers' frames in,
    # moving a line of llama.py changes three lines of the text (PR 28).
    # Keep the innermost frame alone, the kernel's own source.
    jax.config.update("jax_traceback_in_locations_limit", 0)
    # And of that frame no file or line: lines added above a kernel in its
    # module renumber the kernel's and change nothing it computes (PR 60:
    # ``ops/delta.py``'s docstring and walk grew above the rule's kernels).
    # Without a user frame a location is unknown; the scopes' names stay.
    from jax._src import source_info_util
    source_info_util.user_frame = lambda traceback: None
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"
    llama._device_capacity = lambda mesh: V5E_BYTES_LIMIT
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        cells = [c for c in json.load(f)["workloads"]
                 if c["traffic"].startswith("train")
                 and (not only or c["name"] in only)]
    os.makedirs(out, exist_ok=True)
    summary = {}
    for cell in cells:
        got = _compile_cell(tree, cell, topo)
        with open(os.path.join(out, cell["name"] + ".hlo.txt"), "w") as f:
            f.write(got["text"])
        summary[cell["name"]] = got["summary"]
        print(cell["name"], json.dumps(got["summary"]), flush=True)
    with open(os.path.join(out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1, sort_keys=True)
    print("remat plan against the compiler, bytes a device:")
    for name, got in summary.items():
        plan = got["remat"]
        if plan:
            print(f"  {name}: {json.dumps(plan['level'])} need "
                  f"{plan['need_bytes']:,} allotted "
                  f"{plan['allotted_bytes']:,} "
                  f"({plan['allotted_over_need']:.4f} of the need)")


def compare(a: str, b: str) -> int:
    def summary(d):
        with open(os.path.join(d, "summary.json")) as f:
            return json.load(f)

    sa, sb = summary(a), summary(b)
    differing = 0
    for name in sorted(set(sa) | set(sb)):
        if name not in sa or name not in sb:    # a cell one tree cannot run
            print(f"{name}: only in {b if name in sb else a} "
                  f"{json.dumps((sb if name in sb else sa)[name])}")
            continue
        same = all(sa[name].get(f) == sb[name].get(f)
                   for f in PROGRAM_FIELDS)
        differing += not same
        print(f"{name}: {'equal' if same else 'DIFFERENT'} "
              f"{json.dumps(sb.get(name))}")
        if not same:
            print(f"  was: {json.dumps(sa[name])}")
            texts = []
            for d in (a, b):
                with open(os.path.join(d, name + ".hlo.txt")) as f:
                    # without the instructions' serial numbers: one more
                    # instruction renumbers every later one of its kind
                    texts.append(SERIAL.sub(r"\1", f.read()).splitlines())
            diff = list(difflib.unified_diff(*texts, lineterm="", n=0))
            changed = [line[1:].strip() for line in diff
                       if line[:1] in "+-" and line[:3] not in ("+++", "---")]
            # a kernel's body is inside its call's line: a PR that edits
            # the flash kernels alone changes these lines and no other
            flash = sum(bool(FLASH_CALL.match(line)) for line in changed)
            print(f"  {len(changed)} changed lines, {flash} of them calls "
                  "of the flash kernels")
            for line in diff[:40]:
                print("  " + line[:240])
    return 1 if differing else 0


def main() -> int:
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=here)
    ap.add_argument("--out")
    ap.add_argument("--cell", action="append", default=[],
                    help="compile this cell alone (may be given again)")
    ap.add_argument("--compare", nargs=2, metavar="DIR")
    args = ap.parse_args()
    if args.compare:
        return compare(*args.compare)
    if not args.out:
        ap.error("--out or --compare")
    compile_cells(os.path.abspath(args.tree), args.out, args.cell)
    return 0


if __name__ == "__main__":
    sys.exit(main())
