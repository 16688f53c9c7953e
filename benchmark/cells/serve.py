"""Runs one serving cell: deploys the engine through ``serve.run`` on a
TPU actor of the runtime, lets the traffic's generator drive it, and
turns what the client saw into observations for the metric readers.

This process never imports jax: the replica holds the chip.
"""

from __future__ import annotations

import json
import os
import random
import shutil
import time
from typing import Any, Dict, List

from benchmark.lib import procs, spec
from benchmark.lib import window as W

START_TIMEOUT_S = 1000


def _replica_call(name: str, method: str, *args, timeout: float = 600):
    """Calls ``method`` on the deployment's one replica, around the
    router (used only for what is not a request: report, trace, counters)."""
    import ray_tpu
    from ray_tpu.serve.controller import CONTROLLER_NAME

    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    _, replicas = ray_tpu.get(controller.get_replicas.remote(name),
                              timeout=30)
    if len(replicas) != 1:
        raise RuntimeError(f"{name}: {len(replicas)} replicas, expected 1")
    return ray_tpu.get(replicas[0][1].call_method.remote(method, args, {}),
                       timeout=timeout)


def _replica_job(name: str, kind: str, *args, timeout: float = 600):
    """Runs a slow call on the replica as a polled job (lib/engine.py)."""
    _replica_call(name, "job_start", kind, *args)
    t0 = time.monotonic()
    while True:
        res = _replica_call(name, "job_poll")
        if res is not None:
            if "error" in res:
                raise RuntimeError(f"{kind} failed in the replica: "
                                   f"{res['error']}")
            return res["ok"]
        if time.monotonic() - t0 > timeout:
            raise RuntimeError(f"{kind} did not end in {timeout:.0f}s")
        time.sleep(0.2)


def _wait_ready(name: str, platform: str, chips: int) -> Dict[str, Any]:
    deadline = time.monotonic() + START_TIMEOUT_S
    while True:
        rep = _replica_call(name, "report")
        if rep["first_error"] is not None:
            raise RuntimeError(f"engine start-up failed:\n{rep['first_error']}")
        if rep["ready"]:
            if rep["platform"] != platform or rep["device_count"] != chips:
                raise RuntimeError(
                    f"the replica runs on platform={rep['platform']!r} "
                    f"({rep['device_kind']!r}) with {rep['device_count']} "
                    f"device(s); the cell needs {chips} x {platform!r}")
            return rep
        if time.monotonic() > deadline:
            raise RuntimeError(f"engine not ready in {START_TIMEOUT_S}s")
        time.sleep(0.25)


def _check_reply_ids(rec: Dict[str, Any], vocab: int) -> bool:
    toks = rec["tokens"]
    return (len(toks) == rec["req"]["max_new_tokens"]
            and all(isinstance(t, int) and 0 <= t < vocab for t in toks))


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    import ray_tpu
    from ray_tpu import serve, state

    from benchmark.lib.engine import TracedPagedEngine

    cell, config, traffic = ctx["cell"], ctx["config"], ctx["traffic"]
    name = cell["name"]
    model = spec.model_sizes(config)
    vocab = model["vocab_size"]
    gen = spec.generator(traffic["kind"])
    schedule = gen.generate(traffic, ctx["seed"], vocab)

    ray_tpu.init(num_workers=2, object_store_memory=256 << 20)
    try:
        if ctx["platform"] == "tpu":
            have = int(state.cluster_resources().get("TPU", 0))
            if have < cell["chips"]:
                raise RuntimeError(
                    f"the runtime found {have} TPU chip(s); the cell needs "
                    f"{cell['chips']}")
        dep = serve.deployment(engine=True, name=name, num_replicas=1,
                               **ctx["resources"])(TracedPagedEngine).bind(
            model_config=ctx["model_config"], eos_id=-1, greedy=True,
            **traffic["engine"])
        handle = serve.run(dep, timeout=START_TIMEOUT_S)
        rep = _wait_ready(name, ctx["platform"], cell["chips"])

        trace_dir = os.path.join(ctx["tmp_dir"], f"trace-{name}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        marks: Dict[str, Any] = {}

        def on_open(t: float) -> None:
            marks["compiles0"] = _replica_call(name, "compile_count")
            marks["stats0"] = _replica_call(name, "stats")
            marks["t_stats0"] = time.monotonic()
            marks["setup_s"] = time.time() - ctx["t_start_wall"]

        def on_tick(t: float) -> None:
            if not ctx["trace"]:
                return
            if "trace_on" not in marks and t >= marks["t_stats0"] + 1.0:
                marks["trace_stats0"] = _replica_call(name, "stats")
                _replica_job(name, "start_trace", trace_dir)
                marks["trace_on"] = time.monotonic()
            elif ("trace_on" in marks and "trace_off" not in marks
                  and t >= marks["trace_on"] + traffic["trace_seconds"]):
                marks["trace_off"] = time.monotonic()
                marks["trace_stats1"] = _replica_call(name, "stats")
                _replica_job(name, "stop_trace")

        load = gen.drive(handle, schedule, ctx["seconds"], on_open, on_tick)
        marks["stats1"] = _replica_call(name, "stats")
        marks["t_stats1"] = time.monotonic()
        compiles = _replica_call(name, "compile_count") - marks["compiles0"]
        if ctx["trace"] and "trace_off" not in marks:
            raise RuntimeError("the window closed before the trace did: "
                               "trace_seconds must be under --seconds")

        records = load["records"]
        win = load["window"]
        summary = W.summarize(records, win)
        counters = {k: marks["stats1"][k] - marks["stats0"][k]
                    for k in marks["stats0"]
                    if isinstance(marks["stats0"][k], (int, float))}
        counters["out_tokens_between_stats"] = W.tokens_in_window(
            [(t, n) for r in records
             for t, n in zip(r["stamps"], r["counts"])],
            (marks["t_stats0"], marks["t_stats1"]))
        ended = [r for r in records if r["done"] or r["error"]]
        failed = [r for r in ended
                  if r["error"] or not _check_reply_ids(r, vocab)]
        print(f"[bench] window {win[1] - win[0]:.3f}s (asked "
              f"{ctx['seconds']}); compilations inside the window: "
              f"{compiles}; requests ended {len(ended)} failed "
              f"{len(failed)}; samples: out_tokens={summary['out_tokens']} "
              f"itl={len(summary['itl_s'])} ttft={len(summary['ttft_s'])} "
              f"tpot={len(summary['tpot_s'])}; engine setup_s="
              f"{rep['setup_s']:.1f}; counters={counters}", flush=True)
        pct = W.percentile
        print("[bench] client statistics of the window (ms): " + " ".join(
            f"{k}={1e3 * v:.2f}" for k, v in (
                ("itl_p50", pct(summary["itl_s"], 50)),
                ("itl_p95", pct(summary["itl_s"], 95)),
                ("itl_p99", pct(summary["itl_s"], 99)),
                ("tpot_p50", pct(summary["tpot_s"], 50)),
                ("ttft_p50", pct(summary["ttft_s"], 50)),
                ("ttft_p90", pct(summary["ttft_s"], 90))) if v is not None)
              + f" out_tok_per_s={summary['out_tokens'] / summary['window_s']:.3f}",
              flush=True)
        with open(os.path.join(ctx["tmp_dir"], f"records-{name}.json"),
                  "w") as f:                 # for reading by hand
            json.dump({"window": win, "nominal_open": load["nominal_open"],
                       "records": [{k: r[k] for k in (
                           "user", "tag", "sent", "stamps", "counts", "done")}
                           | {"prompt": len(r["req"]["prompt"]),
                              "budget": r["req"]["max_new_tokens"]}
                           for r in records]}, f)
        for r in failed[:5]:
            print(f"[bench] failed request: tag={r['tag']} "
                  f"error={r['error']} n_tokens={len(r['tokens'])} "
                  f"budget={r['req']['max_new_tokens']}", flush=True)

        # ---- correctness, after the window
        deadline = time.monotonic() + 120
        while True:                       # cancelled requests have left
            st = _replica_call(name, "stats")
            if not (st["active"] or st["queued"] or st["inflight_chunks"]):
                break
            if time.monotonic() > deadline:
                raise RuntimeError(f"engine did not drain: {st}")
            time.sleep(0.1)
        good = [r for r in ended if r not in failed]
        rng = random.Random(ctx["seed"])
        chk = traffic["check"]
        sample: List[Dict[str, Any]] = []
        for tag in sorted({r["tag"] for r in good}):
            pool = [r for r in good if r["tag"] == tag
                    and len(r["req"]["prompt"]) + len(r["tokens"])
                    <= chk["pad_to"]]
            sample += rng.sample(pool, min(chk["samples_per_tag"], len(pool)))
        correct = not failed and bool(sample)
        to_check = [(r["tag"], r["req"]["prompt"], r["tokens"])
                    for r in sample]
        if sample:
            # the same request again, unary, now over its own cached
            # prefix: its tokens go through the same reference check (a
            # near-tie may resolve the other way, so identity of the two
            # replies is reported and not required)
            r = sample[0]
            again = handle.remote(
                r["req"]["prompt"],
                max_new_tokens=r["req"]["max_new_tokens"]).result(timeout=300)
            if not isinstance(again, dict) or \
                    len(again["tokens"]) != len(r["tokens"]):
                print(f"[bench] repeat failed: {again!r}"[:300], flush=True)
                correct = False
            else:
                agree = 0
                for x, y in zip(again["tokens"], r["tokens"]):
                    if x != y:
                        break
                    agree += 1
                print(f"[bench] greedy repeat: first {agree} of "
                      f"{len(r['tokens'])} tokens identical", flush=True)
                to_check.append((r["tag"] + "-repeat", r["req"]["prompt"],
                                 again["tokens"]))
        for tag, prompt, tokens in to_check:
            t_ref = time.monotonic()
            res = _replica_job(name, "reference_check", prompt, tokens,
                               chk["pad_to"])
            ok = res["max_regret"] <= chk["max_regret"]
            print(f"[bench] reference check tag={tag} prompt={len(prompt)} "
                  f"{res} ok={ok} ({time.monotonic() - t_ref:.1f}s)",
                  flush=True)
            correct = correct and ok
        device = _replica_call(name, "device_report")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
        ended = procs.wait_for_children()
        print(f"[bench] after the runtime's shutdown {ended['reaped']:.0f} "
              f"child process(es) were waited for during "
              f"{ended['seconds']:.2f}s", flush=True)

    obs_trace = {}
    if ctx["trace"]:
        obs_trace = {
            "trace_span": (marks["trace_on"], marks["trace_off"]),
            "trace_counters": {
                k: marks["trace_stats1"][k] - marks["trace_stats0"][k]
                for k in ("steps", "prefill_tokens_computed",
                          "prefix_hit_tokens")
                if k in marks["trace_stats0"]}}
    if compiles:
        raise RuntimeError(f"{compiles} program(s) compiled inside the "
                           f"measured window")
    return {
        "correct": correct, "attempted": len(ended), "failed": len(failed),
        "device": device, "setup_s": marks["setup_s"],
        "obs": {"client": summary, "counters": counters, "model": model,
                "traffic": traffic, "cell": cell, "records": records,
                "window": win, **obs_trace},
        "trace_dir": trace_dir if ctx["trace"] else None,
    }
