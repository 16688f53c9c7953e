"""Continuous-batching LLM engine: the TPU-native Serve replica body.

Static-shape design (see models/llama_decode.py): a fixed set of sequence
slots shares one decode program; new requests join between decode chunks by
prefilling (bucketed prompt padding → a handful of prefill compilations)
into a free slot. This is continuous batching in the vLLM sense — requests
enter and leave the running batch at token granularity — built the TPU way
(static shapes, a handful of compiled programs).

Decode is a PIPELINED ON-DEVICE LOOP (the round-5 redesign): each dispatch
runs k decode steps in one program whose sampled tokens feed back through
the program's own outputs, so chunk N+1 chains to chunk N entirely on
device — the host never syncs between chunks. Generated tokens stream back
through async device→host copies reaped one pipeline-depth behind the
dispatch frontier. Steady-state cost per token is therefore the DEVICE
step time, not the host's dispatch round-trip. Admission sampling (the
prompt's first token) runs on device too; its value is reaped
asynchronously like chunk tokens.

Runs inside a Serve ReplicaActor via the submit/collect mailbox: ``submit``
enqueues and returns immediately; a background thread drives the engine;
``collect`` drains finished generations. The router polls collect() so the
replica's actor queue never blocks behind a generation (reference
analogue: serve.llm / vLLM engine loop on GPU; resident-loop philosophy:
/root/reference/python/ray/dag/compiled_dag_node.py:482).
"""

from __future__ import annotations

import collections
import os
import queue
import sys
import threading
import time
import traceback
from typing import Any, Dict, List, Optional

from ray_tpu.util import tracing


def _bucket(n: int, buckets: List[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


class LLMEngine:
    """Deployment class: continuous-batched generation on the tiny-to-8B
    Llama family. Construct via serve.deployment(engine=True)."""

    def __init__(self, model_config: Optional[dict] = None,
                 num_slots: int = 8, max_len: int = 256,
                 prefill_buckets: Optional[List[int]] = None,
                 max_new_tokens: int = 32, eos_id: int = -1,
                 greedy: bool = True, chunk_steps: int = 8,
                 tp: int = 1, mesh=None, top_k: int = 0,
                 sampling_seed: int = 0, pipeline_depth: int = 2):
        from ray_tpu.core.compile_cache import ensure_compile_cache

        self._t_init = time.monotonic()
        ensure_compile_cache()
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models import llama, llama_decode

        # _precompile's programs, and any a request compiles later, are
        # spans of timeline()
        tracing.watch_jax()

        cfg_kw = dict(model_config or {})
        hf_model = cfg_kw.pop("hf_model", None)
        preset = cfg_kw.pop("preset", "tiny")
        quantize = cfg_kw.pop("quantize", None)
        for key in ("dtype", "param_dtype"):
            if isinstance(cfg_kw.get(key), str):
                cfg_kw[key] = getattr(jnp, cfg_kw[key])
        hf_params = None
        if hf_model is not None:
            # serve a real checkpoint: anything from_pretrained accepts
            # (models/hf_weights.py maps the state dict onto our pytree)
            from dataclasses import replace as _replace

            from ray_tpu.models.hf_weights import from_hf, hf_model_type

            # refuse BEFORE from_hf materializes a multi-GB checkpoint
            mt = hf_model_type(hf_model)
            if mt not in ("llama", "qwen2", "gemma"):
                raise ValueError(
                    "the continuous-batching engine serves llama-family "
                    f"dense checkpoints (llama/qwen2/gemma); got {mt!r}")
            cfg, hf_params = from_hf(
                hf_model, dtype=cfg_kw.pop("param_dtype", None))
            cfg = _replace(cfg, **cfg_kw)
        else:
            cfg = getattr(llama.LlamaConfig, preset)(**cfg_kw)
        self._cfg = cfg
        # tensor-parallel serving (a v5e-4 host serving one model): weights
        # and KV cache shard over a tp mesh; XLA emits the per-layer
        # all-reduces over ICI. tp=1 keeps the single-chip path unchanged.
        if mesh is None and tp > 1:
            from ray_tpu.parallel import MeshSpec, build_mesh

            devs = jax.devices()
            if len(devs) < tp:
                raise ValueError(
                    f"tp={tp} needs {tp} devices, found {len(devs)}")
            mesh = build_mesh(MeshSpec({"tp": tp}), devices=devs[:tp])
        self._mesh = mesh
        if mesh is not None and cfg.prefill_flash is not False:
            # pallas prefill cannot ride GSPMD sharding; TP serving
            # ALWAYS uses the plain-XLA attention, overriding even an
            # explicit prefill_flash=True (LlamaConfig documents this)
            from dataclasses import replace as _rp

            cfg = _rp(cfg, prefill_flash=False)
            self._cfg = cfg
        self._params = (hf_params if hf_params is not None else
                        llama.init_params(cfg, jax.random.PRNGKey(0)))
        if quantize is not None:
            # weight-only int8 serving. On the pipelined decode
            # (in-place cache scatter) XLA fuses the dequant into the
            # dots and the halved weight reads land: ITL p50 2.9 ms vs
            # 3.6 ms bf16 at 1B on v5e (round 5, old machine) — plus the
            # HBM CAPACITY win (weights shrink 2x: 8B int8 in ~8 GB, or
            # longer KV caches). Quality: ~1e-2 relative logit error
            # (pinned in tests). Opt-in.
            if quantize != "int8":
                raise ValueError(
                    f"unsupported quantize={quantize!r} (only 'int8')")
            if mesh is not None or tp > 1:
                raise ValueError(
                    "quantize='int8' currently serves single-chip "
                    "(tp=1); drop quantize or tp")
            self._params = jax.jit(
                llama_decode.quantize_decode_params)(self._params)
        if mesh is not None:
            # shard NOW and drop the unsharded copy — keeping both would
            # hold 1x + 1/tp weights on chip 0, defeating TP's HBM saving
            self._params = jax.device_put(
                self._params, llama.param_shardings(cfg, mesh))
        self._num_slots = num_slots
        self._max_len = max_len
        # max_len-1 terminates the bucket list so over-length (truncated)
        # prompts still land on a static shape — never a novel compilation
        self._buckets = sorted(set(
            [b for b in (prefill_buckets or [32, 64, 128])
             if b < max_len] + [max_len - 1]))
        self._max_new = max_new_tokens
        self._eos = eos_id
        self._greedy = greedy
        # clamp: top_k >= vocab would fail at trace time and
        # loop the engine on per-tick compile errors
        self._top_k = min(int(top_k), cfg.vocab_size - 1)
        if self._top_k < 0:
            self._top_k = 0
        self._seed = int(sampling_seed)
        self._jnp = jnp

        self._init_programs()
        # Tokens decoded per dispatched program. Chunks chain on device,
        # so throughput is chunk-size-insensitive once the pipeline is
        # deep enough to cover the dispatch round-trip; larger chunks
        # mainly reduce host work. Normalized to a power of two: chunk
        # lengths are compile-time static and bucketed, so only log2
        # programs ever exist.
        chunk_steps = max(1, int(chunk_steps))
        self._chunk_steps = 1 << (chunk_steps.bit_length() - 1)
        # in-flight device work the host has dispatched but not reaped;
        # depth 2 keeps the device busy across one readback round-trip
        self._depth = max(1, int(pipeline_depth))
        self._inflight: "collections.deque[tuple]" = collections.deque()

        # on-device chain state: the last sampled token + next write
        # position per slot, produced by one program and consumed by the
        # next without ever visiting the host
        self._chain_toks = jnp.zeros((num_slots,), jnp.int32)
        self._chain_pos = jnp.zeros((num_slots,), jnp.int32)
        self._zero_key = jnp.zeros((2,), jnp.uint32)

        # jitted helpers: splice admitted slots into the chain state and
        # pick the prompt's first token on device (no host round-trip in
        # the admission path either)
        def merge_admitted(toks, pos, firsts, slots, valid, new_pos):
            idx = jnp.where(valid, slots, toks.shape[0])
            return (toks.at[idx].set(firsts, mode="drop"),
                    pos.at[idx].set(new_pos, mode="drop"))

        # named functions, not lambdas: a program's name in a profiler
        # trace is its function's (``jit_first_argmax``)
        def first_argmax(lg):
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)

        tk = self._top_k

        def first_sample(lg, key, temps):
            return llama_decode.sample_tokens(lg, key, temps, tk)

        self._merge_j = jax.jit(merge_admitted)
        self._argmax_j = jax.jit(first_argmax)
        self._sample_j = jax.jit(first_sample)

        # slot bookkeeping (host side)
        self._free = list(range(num_slots))
        self._slot_req: Dict[int, str] = {}
        self._slot_tokens: Dict[int, List[int]] = {}
        self._slot_budget: Dict[int, int] = {}
        self._slot_pos: Dict[int, int] = {}     # next write pos (speculative)
        self._slot_plen: Dict[int, int] = {}    # prompt length
        self._sched: Dict[int, int] = {}        # tokens dispatched (incl 1st)
        self._slot_start: Dict[int, float] = {}
        self._slot_ttft: Dict[int, float] = {}
        self._slot_temp: Dict[int, float] = {}
        self._slot_stop: Dict[int, frozenset] = {}

        self._in: "queue.Queue[tuple]" = queue.Queue()
        self._cancelled: Dict[str, float] = {}  # req_id -> cancel time
        self._done: Dict[str, Any] = {}
        self._seen_ids: Dict[str, float] = {}  # req_id -> submit time
        self._done_lock = threading.Lock()
        self._steps = 0
        self._completed = 0
        # what the tick does with its time and its queue, monotonic,
        # read through stats(): a tick's phases in ns, the in-flight
        # queue's length summed once a tick, and slot-ticks (one per
        # occupied slot per tick; "drained" = every token of the slot's
        # budget is dispatched and the slot waits for its last reap)
        self._ticks = 0
        self._inflight_depth_sum = 0
        self._steps_dispatched = 0
        self._admit_ns = 0
        self._dispatch_ns = 0
        self._reap_wait_ns = 0
        self._sleep_ns = 0
        self._slot_ticks_occupied = 0
        self._slot_ticks_drained = 0
        self._trace: Dict[str, Any] = {"state": "off", "dir": None,
                                       "error": None}
        self._key_ctr = 0
        self._stop = False
        # start-up outcome, read by report(): how long construction +
        # _precompile took (None until it has finished) and the first
        # device-program failure (compile or run) with traceback
        self._setup_s: Optional[float] = None
        self._first_error: Optional[str] = None
        self._mosaic_calls: Optional[Dict[str, int]] = None
        self._mosaic_thread: Optional[threading.Thread] = None
        import weakref

        from ray_tpu import metrics

        # weakly: the registry must not keep a shut-down engine's weights
        # and cache alive (a dead source raises and is left out)
        me = weakref.ref(self)
        metrics.REGISTRY.register_source("rtpu_engine",
                                         lambda: me().stats())
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="llm-engine")
        self._thread.start()

    def _init_programs(self):
        """Build the compiled-program set and device cache state.
        PagedLLMEngine overrides this (and the admission/dispatch
        internals) to swap the dense slot cache for the page pool."""
        from ray_tpu.models import llama_decode

        # the single-step decode program is unused since the pipelined
        # loop runs k==1 through the chunk program (one fewer compile)
        (self._prefill_batch, self._insert_many, _,
         self._decode_chunk) = \
            llama_decode.make_engine_fns(self._cfg, self._params,
                                         self._num_slots, self._max_len,
                                         mesh=self._mesh)
        # burst admission: up to this many prompts prefill in ONE batched
        # program call (2 compiled batch sizes: 1 and this max)
        self._admit_batch = max(1, min(8, self._num_slots))
        self._cache = llama_decode.init_cache(
            self._cfg, self._num_slots, self._max_len, mesh=self._mesh)

    def _lowered_programs(self) -> Dict[str, Any]:
        """The engine's prefill (per bucket) and decode programs, lowered
        on abstract arguments (the live cache is donated by the engine
        thread; report() runs on a request thread)."""
        import jax

        jnp = self._jnp
        sds = jax.ShapeDtypeStruct
        S = self._num_slots
        cache = self._abstract_cache()
        out = {f"prefill[{b}]": self._prefill_batch.lower(
            sds((1, b), jnp.int32), sds((1,), jnp.int32))
            for b in self._buckets}
        out["decode"] = self._decode_chunk.lower(
            cache, sds((S,), jnp.int32), sds((S,), jnp.int32),
            sds((S,), bool), 1, self._zero_key, sds((S,), jnp.float32),
            0, False)
        return out

    def _abstract_cache(self):
        import jax

        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self._cache)

    def report(self) -> dict:
        """What this engine is running on and how start-up went — the
        serving half of chip_smoke.py's evidence. ``mosaic_calls`` counts
        the Mosaic (compiled Pallas) custom calls in each program's
        compiled text: 0 means that program took the XLA reference path
        (off-TPU, a bucket the flash kernel's block size does not
        divide, or a kernel in interpret mode). It is None until the
        count, started by the first call after start-up, has finished."""
        import jax

        devs = jax.devices()
        out = {
            "pid": os.getpid(),
            "platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs),
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
            "ready": self._setup_s is not None,
            "setup_s": self._setup_s,
            "first_error": self._first_error,
            "mosaic_calls": self._mosaic_calls,
            "trace": dict(self._trace),
            "stats": self.stats(),
        }
        if self._setup_s is not None and self._mosaic_thread is None:
            # counted once (the programs never change), off this request
            # thread: compiling here would starve the controller's pings
            self._mosaic_thread = threading.Thread(
                target=self._count_mosaic_calls, daemon=True,
                name="llm-engine-report")
            self._mosaic_thread.start()
        return out

    def _count_mosaic_calls(self):
        try:
            self._mosaic_calls = {
                name: lowered.compile().as_text().count("tpu_custom_call")
                for name, lowered in self._lowered_programs().items()}
        except Exception as e:  # noqa: BLE001 — reported, not raised
            self._note_error("report", e)

    def start_trace(self, trace_dir: str) -> None:
        """Start jax's profiler in this process, writing under
        ``trace_dir``; returns at once. Starting (and stopping) takes
        seconds, longer than a replica's health ping allows a call to
        last, so it runs on a thread of its own: ``report()["trace"]``
        says ``starting``, then ``on`` (or ``off`` with an ``error``).
        While it is on, every span of this process is kept."""
        self._trace_job("starting", "on", tracing.start_profile, trace_dir)

    def stop_trace(self) -> None:
        """Stop the profiler; ``report()["trace"]["state"]`` goes from
        ``stopping`` to ``off`` once the trace is written."""
        self._trace_job("stopping", "off", tracing.stop_profile)

    def _trace_job(self, during: str, after: str, fn, *args) -> None:
        if self._trace["state"] in ("starting", "stopping"):
            raise RuntimeError(f"the profiler is {self._trace['state']}")
        self._trace.update(state=during, error=None,
                           dir=args[0] if args else self._trace["dir"])

        def work() -> None:
            try:
                fn(*args)
                self._trace["state"] = after
            except Exception as e:  # noqa: BLE001 — reported, not raised
                self._trace.update(state="off", error=repr(e))

        threading.Thread(target=work, daemon=True,
                         name=f"llm-engine-trace-{during}").start()

    def timeline(self) -> List[dict]:
        """The spans this process kept (chrome-trace form): everything
        of a profiler window, or since start-up under the
        ``task_events_enabled`` flag; always, each program's trace,
        lowering and compile (``rtpu.jax.*``: ``_precompile``'s, and
        any a request made later)."""
        return tracing.chrome_events()

    def _note_error(self, where: str, exc: BaseException) -> None:
        """Keep the FIRST device-program failure (later ones are usually
        its echoes) and put every one in the worker's log; the engine
        itself lives on."""
        msg = f"{where}: " + "".join(traceback.format_exception(exc))
        print(f"[llm-engine] {msg}", file=sys.stderr, flush=True)
        if self._first_error is None:
            self._first_error = msg

    # ---- mailbox (called from the actor's request thread) ------------------

    def submit(self, req_id: str, prompt_tokens: List[int],
               max_new_tokens: Optional[int] = None,
               temperature: float = 0.0,
               stop_ids: Optional[List[int]] = None) -> None:
        """temperature 0 = greedy; >0 samples (engine-level ``top_k``
        masks the tail). Mixed batches share one decode program — each
        slot applies its own temperature on-device. ``stop_ids``: extra
        per-request stop tokens besides the engine's eos_id (generation
        ends when any is produced; the stop token is kept in the
        output, reference: vLLM SamplingParams.stop_token_ids).

        ``req_id`` is the request's identity: a duplicate submit (router
        replay racing a lost-but-delivered first submit) is dropped so
        at-least-once delivery still runs the generation exactly once —
        the original's result lands in the mailbox under the same id."""
        now = time.monotonic()
        with tracing.span("rtpu.engine.submit", id=req_id,
                          prompt_tokens=len(prompt_tokens)):
            with self._done_lock:
                if len(self._seen_ids) > 2048:
                    cutoff = now - 600.0
                    self._seen_ids = {
                        r: t for r, t in self._seen_ids.items()
                        if t > cutoff}
                if req_id in self._seen_ids:
                    return
                self._seen_ids[req_id] = now
            self._in.put((req_id, list(prompt_tokens),
                          max_new_tokens or self._max_new, now,
                          float(temperature),
                          frozenset(int(t) for t in (stop_ids or ()))))

    def collect(self, req_ids: Optional[List[str]] = None) -> Dict[str, Any]:
        """Drain finished requests. With ``req_ids``, only those are
        removed — other consumers' results stay (multiple routers may poll
        the same engine)."""
        with self._done_lock:
            if req_ids is None:
                out, self._done = self._done, {}
            else:
                out = {r: self._done.pop(r) for r in req_ids
                       if r in self._done}
        return out

    def peek(self, req_ids: Optional[List[str]] = None,
             since: Optional[Dict[str, int]] = None) -> Dict[str, Any]:
        """Non-destructive progress snapshot for streaming consumers:
        {req_id: {"tokens": [...], "offset": k, "done": bool}} where
        ``tokens`` are those from each request's ``since[req_id]`` offset
        on (a poller then transfers O(new), not O(all-so-far) per poll).
        Finished requests stay in the mailbox until ``collect``."""
        since = since or {}

        def view(rid, toks, done):
            off = since.get(rid, 0)
            return {"tokens": list(toks[off:]), "offset": off,
                    "done": done}

        out: Dict[str, Any] = {}
        # in-flight slots (list() copies under the GIL; the engine thread
        # only appends)
        for slot, rid in list(self._slot_req.items()):
            if req_ids is not None and rid not in req_ids:
                continue
            toks = self._slot_tokens.get(slot)
            if toks is not None:
                out[rid] = view(rid, toks, False)
        with self._done_lock:
            for rid, res in self._done.items():
                if req_ids is not None and rid not in req_ids:
                    continue
                if isinstance(res, Exception):
                    out[rid] = {"error": repr(res), "done": True}
                else:
                    out[rid] = view(rid, res["tokens"], True)
        return out

    def cancel(self, req_id: str) -> None:
        """Abort a request: the ENGINE THREAD notices the cancel mark at
        its next tick — a generating slot is finished immediately with
        its result discarded (tokens still in the device pipeline for it
        are dropped at reap by the slot→request match), a queued request
        is dropped at admission, and a finished-but-uncollected result is
        removed. Mark-and-pop happen under one lock with the finish
        path's check-and-insert, so a result can never slip into the
        mailbox after its cancel."""
        with self._done_lock:
            if self._done.pop(req_id, None) is None:
                self._cancelled[req_id] = time.monotonic()

    def stats(self) -> dict:
        return {"active": self._num_slots - len(self._free),
                "queued": self._in.qsize(), "steps": self._steps,
                "completed": self._completed,
                "slots": self._num_slots,
                "inflight_chunks": len(self._inflight),
                "ticks": self._ticks,
                "inflight_depth_sum": self._inflight_depth_sum,
                "steps_dispatched": self._steps_dispatched,
                "admit_ns": self._admit_ns,
                "dispatch_ns": self._dispatch_ns,
                "reap_wait_ns": self._reap_wait_ns,
                "sleep_ns": self._sleep_ns,
                "slot_ticks_occupied": self._slot_ticks_occupied,
                "slot_ticks_drained": self._slot_ticks_drained}

    def shutdown(self):
        self._stop = True

    # ---- engine loop -------------------------------------------------------

    def _next_key(self):
        """Legacy uint32[2] PRNG key built host-side (a PRNGKey() eager
        op would cost a device dispatch per sampled tick)."""
        import numpy as np

        self._key_ctr += 1
        return self._jnp.asarray(np.array(
            [self._seed & 0xFFFFFFFF, self._key_ctr & 0xFFFFFFFF],
            np.uint32))

    def _has_parked_requests(self) -> bool:
        """Whether admission is holding requests outside ``_in`` (the
        paged engine parks pool-exhausted requests for head-of-line
        retry); saturation-sensitive decode chunking consults this."""
        return False

    def _admit(self) -> bool:
        """Prefill waiting requests into free slots; returns True if any.

        Requests are admitted in batches: up to ``_admit_batch`` waiting
        prompts run through ONE batched prefill + insert + first-token
        sample, all on device; the first token's value is reaped
        asynchronously with the decode pipeline, so admission never
        blocks the engine thread on a device round-trip.
        """
        import numpy as np

        jnp = self._jnp
        admitted = False
        while self._free and not self._in.empty():
            # pull up to min(free slots, admit batch) waiting requests
            pending = []
            while (len(pending) < min(len(self._free), self._admit_batch)
                   and not self._in.empty()):
                try:
                    pending.append(self._in.get_nowait())
                except queue.Empty:
                    break
            if not pending:
                break
            batch = []   # (req_id, toks, max_new, t0, temp, stop, slot)
            for req_id, toks, max_new, t0, temp, stop in pending:
                with self._done_lock:
                    was_cancelled = (
                        self._cancelled.pop(req_id, None) is not None)
                if was_cancelled:
                    continue  # dropped pre-admission
                try:
                    toks = [int(t) for t in toks]
                    if not toks:
                        raise ValueError("empty prompt")
                except Exception as e:  # noqa: BLE001
                    with self._done_lock:
                        self._done[req_id] = ValueError(
                            f"request rejected: {e!r}")
                    continue
                if len(toks) >= self._max_len:
                    toks = toks[: self._max_len - 1]
                batch.append((req_id, toks, max_new, t0, temp, stop,
                              self._free.pop()))
            if not batch:
                continue
            try:
                # one code path for both sizes: the batched prefill takes
                # the last-token index as a TRACED argument, so prompt
                # length never mints a new program (a python-int slice
                # like logits[len-1] would compile per distinct length,
                # paid inside TTFT)
                B = 1 if len(batch) == 1 else self._admit_batch
                P = _bucket(max(len(t) for _, t, _, _, _, _, _ in batch),
                            self._buckets)
                rows = np.zeros((B, P), np.int32)
                last = np.zeros((B,), np.int32)
                slots = np.zeros((B,), np.int32)
                valid = np.zeros((B,), bool)
                temps = np.zeros((B,), np.float32)
                plens = np.zeros((B,), np.int32)
                for i, (_, toks, _, _, temp, _, slot) in enumerate(batch):
                    rows[i, :len(toks)] = toks
                    last[i] = len(toks) - 1
                    slots[i], valid[i] = slot, True
                    temps[i] = temp
                    plens[i] = len(toks)
                with tracing.span("rtpu.engine.prefill",
                                  tokens=int(plens.sum()), requests=len(batch)):
                    logits, kv = self._prefill_batch(jnp.asarray(rows),
                                                     jnp.asarray(last))
                slots_d = jnp.asarray(slots)
                valid_d = jnp.asarray(valid)
                self._cache = self._insert_many(
                    self._cache, kv, slots_d, valid_d)
                if temps.any():
                    firsts = self._sample_j(logits, self._next_key(),
                                            jnp.asarray(temps))
                else:
                    firsts = self._argmax_j(logits)
                self._chain_toks, self._chain_pos = self._merge_j(
                    self._chain_toks, self._chain_pos, firsts,
                    slots_d, valid_d, jnp.asarray(plens))
                try:
                    firsts.copy_to_host_async()
                except Exception:  # noqa: BLE001 — optional fast path
                    pass
            except Exception as e:  # noqa: BLE001 — fail THESE requests
                self._note_error("prefill", e)
                for req_id, _, _, _, _, _, slot in batch:
                    self._free.append(slot)
                    with self._done_lock:
                        self._done[req_id] = ValueError(
                            f"request rejected: {e!r}")
                continue
            entries = []
            for req_id, toks, max_new, t0, temp, stop, slot in batch:
                self._slot_temp[slot] = temp
                self._slot_stop[slot] = stop
                self._slot_req[slot] = req_id
                self._slot_tokens[slot] = []
                self._slot_budget[slot] = max_new
                self._slot_pos[slot] = len(toks)
                self._slot_plen[slot] = len(toks)
                self._sched[slot] = 1
                self._slot_start[slot] = t0
                entries.append((req_id, slot))
                admitted = True
            self._inflight.append(("admit", {"firsts": firsts,
                                             "batch": entries}))
        return admitted

    def _maybe_finish(self, slot: int, last_token: int) -> bool:
        toks = self._slot_tokens[slot]
        if (last_token == self._eos
                or last_token in self._slot_stop.get(slot, ())
                or len(toks) >= self._slot_budget[slot]
                or self._slot_plen[slot] + len(toks) >= self._max_len - 1):
            req_id = self._slot_req.pop(slot)
            ttft = self._slot_ttft.get(
                slot, time.monotonic() - self._slot_start[slot])
            with self._done_lock:
                if self._cancelled.pop(req_id, None) is not None:
                    pass  # aborted: drop silently
                else:
                    self._completed += 1
                    self._done[req_id] = {
                        "tokens": list(toks),
                        "ttft_s": ttft,
                        "latency_s": (time.monotonic()
                                      - self._slot_start[slot]),
                    }
            tracing.mark("rtpu.engine.finish", id=req_id, tokens=len(toks))
            self._drop_slot(slot)
            return True
        return False

    def _drop_slot(self, slot: int):
        for d in (self._slot_tokens, self._slot_budget, self._slot_pos,
                  self._slot_plen, self._sched, self._slot_start,
                  self._slot_ttft, self._slot_temp, self._slot_stop):
            d.pop(slot, None)
        self._free.append(slot)

    def _precompile(self):
        """Compile every program this engine can ever run — each
        power-of-two chunk bucket in both greedy and sampling variants,
        and each prefill bucket with its admission helpers — at startup,
        so no request stalls behind a first-occurrence XLA compile
        mid-serve."""
        import numpy as np

        jnp = self._jnp
        S = self._num_slots
        toks = jnp.zeros((S,), jnp.int32)
        poss = jnp.zeros((S,), jnp.int32)
        act = jnp.zeros((S,), bool)  # inactive: cache unchanged
        zero_t = jnp.zeros((S,), jnp.float32)
        key0 = self._zero_key
        k = 1
        while k <= self._chunk_steps:
            self._cache, out, self._chain_toks, self._chain_pos = \
                self._decode_chunk(self._cache, toks, poss, act, k,
                                   key0, zero_t, 0, False)
            np.asarray(out)
            self._cache, out, self._chain_toks, self._chain_pos = \
                self._decode_chunk(self._cache, toks, poss, act, k,
                                   key0, zero_t, self._top_k, True)
            np.asarray(out)
            k *= 2
        sizes = sorted({1, self._admit_batch})
        for b in self._buckets:
            for B in sizes:
                # admission path per (batch-size, bucket): prefill_batch +
                # insert_many + sample/argmax + merge — ALL compile per
                # shape, and any one left cold lands its compile inside
                # a TTFT
                lg, kvb = self._prefill_batch(
                    jnp.zeros((B, b), jnp.int32),
                    jnp.zeros((B,), jnp.int32))
                sl = jnp.zeros((B,), jnp.int32)
                vl = jnp.zeros((B,), bool)
                f1 = self._argmax_j(lg)
                f2 = self._sample_j(lg, key0, jnp.zeros((B,), jnp.float32))
                self._chain_toks, self._chain_pos = self._merge_j(
                    self._chain_toks, self._chain_pos, f1, sl, vl,
                    jnp.zeros((B,), jnp.int32))
                self._chain_toks, self._chain_pos = self._merge_j(
                    self._chain_toks, self._chain_pos, f2, sl, vl,
                    jnp.zeros((B,), jnp.int32))
                self._cache = self._insert_many(self._cache, kvb, sl, vl)
        np.asarray(self._cache["k"][0, 0, 0, 0, 0])

    def _reset_device_state(self):
        """Recover from a failed device program: donation may have
        consumed the cache buffer mid-flight, so rebuild everything the
        dispatch chain touches."""
        from ray_tpu.models import llama_decode

        jnp = self._jnp
        self._inflight.clear()
        self._cache = llama_decode.init_cache(
            self._cfg, self._num_slots, self._max_len, mesh=self._mesh)
        self._chain_toks = jnp.zeros((self._num_slots,), jnp.int32)
        self._chain_pos = jnp.zeros((self._num_slots,), jnp.int32)

    def _run(self):
        import numpy as np

        jnp = self._jnp
        try:
            self._precompile()
        except Exception as e:  # noqa: BLE001 — lazily compile instead
            self._note_error("precompile", e)
        self._setup_s = time.monotonic() - self._t_init
        while not self._stop:
            try:
                self._tick(np, jnp)
            except Exception as e:  # noqa: BLE001 — fail in-flight, live on
                self._note_error("engine step", e)
                failed = list(self._slot_req.items())
                with self._done_lock:
                    for slot, req_id in failed:
                        # cancelled requests get NO result even on engine
                        # failure (cancel()'s contract), and their mark is
                        # consumed so the req_id can be reused
                        if self._cancelled.pop(req_id, None) is None:
                            self._done[req_id] = RuntimeError(
                                f"engine step failed: {e!r}")
                for slot, _ in failed:
                    self._slot_req.pop(slot, None)
                    self._drop_slot(slot)
                self._reset_device_state()

    def _prepare_dispatch(self, elig: List[int], k: int) -> List[int]:
        """Hook: reserve whatever the chunk needs for ``k`` more tokens
        per slot; returns the subset actually dispatchable now (the
        paged engine grows block tables here and stalls slots the page
        pool cannot cover)."""
        return elig

    def _dispatch_stalled(self, elig: List[int]) -> None:
        """Hook: called when _prepare_dispatch returned no slots."""

    def _run_chunk(self, jnp, act, k, key, temps, sampling):
        """Hook: invoke the decode-chunk program (the paged engine adds
        its block-table argument); must update the cache + chain state
        and return the [k, S] token output array."""
        (self._cache, out, self._chain_toks, self._chain_pos) = \
            self._decode_chunk(
                self._cache, self._chain_toks, self._chain_pos,
                act, k, key, temps,
                self._top_k if sampling else 0, sampling)
        return out

    def _dispatch(self, np, jnp) -> bool:
        """Dispatch one decode chunk over the eligible slots; the chunk's
        inputs are the previous chunk's DEVICE outputs (plus any
        admission merges), so this enqueues work without waiting."""
        elig = [s for s in self._slot_req
                if self._sched[s] < self._slot_budget[s]
                and self._slot_pos[s] < self._max_len - 1]
        if not elig:
            return False
        # With requests waiting (the pool is saturated — _admit just
        # drained the queue into any free slots), chunk toward the
        # earliest KNOWN finish (token budgets are known up front) so the
        # waiter is admitted promptly; chunk lengths round DOWN to a
        # power of two (static jit arg; only the precompiled buckets may
        # run). An unpredictable mid-chunk EOS delays admission by one
        # chunk plus the pipeline depth at most.
        k = self._chunk_steps
        if not self._in.empty() or self._has_parked_requests():
            to_finish = min(self._slot_budget[s] - self._sched[s]
                            for s in elig)
            k = max(1, min(k, to_finish))
        k = min(k, max(1, self._max_len - 1
                       - max(self._slot_pos[s] for s in elig)))
        k = 1 << (k.bit_length() - 1)
        ready = self._prepare_dispatch(elig, k)
        if not ready:
            self._dispatch_stalled(elig)
            return False
        S = self._num_slots
        act = np.zeros((S,), bool)
        temps = np.zeros((S,), np.float32)
        for s in ready:
            act[s] = True
            temps[s] = self._slot_temp.get(s, 0.0)
        sampling = bool(temps.any())
        key = self._next_key() if sampling else self._zero_key
        out = self._run_chunk(jnp, jnp.asarray(act), k, key,
                              jnp.asarray(temps), sampling)
        try:
            out.copy_to_host_async()
        except Exception:  # noqa: BLE001 — optional fast path
            pass
        self._inflight.append(("chunk", {
            "out": out, "slots": {s: self._slot_req[s] for s in ready}}))
        self._steps_dispatched += k
        for s in ready:
            self._slot_pos[s] += k
            self._sched[s] += k
        return True

    def _reap(self, np):
        """Block on the OLDEST in-flight record (its async copy typically
        already landed) and fold its tokens into the slot bookkeeping.
        The slot→request match drops tokens for slots recycled since the
        record was dispatched."""
        kind, rec = self._inflight.popleft()
        t0 = time.monotonic_ns()
        arr = np.asarray(rec["firsts" if kind == "admit" else "out"])
        self._reap_wait_ns += time.monotonic_ns() - t0
        if kind == "admit":
            now = time.monotonic()
            for i, (req_id, slot) in enumerate(rec["batch"]):
                if self._slot_req.get(slot) != req_id:
                    continue
                self._slot_ttft[slot] = now - self._slot_start[slot]
                tracing.mark("rtpu.engine.first_token", id=req_id)
                tok = int(arr[i])
                self._slot_tokens[slot].append(tok)
                self._maybe_finish(slot, tok)
            return
        out = arr  # [k, S]
        self._steps += out.shape[0]
        for slot, req_id in rec["slots"].items():
            if self._slot_req.get(slot) != req_id:
                continue
            for step in range(out.shape[0]):
                tok = int(out[step, slot])
                self._slot_tokens[slot].append(tok)
                if self._maybe_finish(slot, tok):
                    break

    def _tick(self, np, jnp):
        # engine-thread cancel handling: finish marked slots immediately
        # (result discarded; tokens still in the device pipeline for the
        # slot are dropped at reap by the request match). Doing this
        # here, where slot bookkeeping is single-threaded, means a cancel
        # can never touch a slot recycled to another request.
        with self._done_lock:
            cancelled = set(self._cancelled)
        if cancelled:
            for slot, rid in list(self._slot_req.items()):
                if rid in cancelled:
                    self._slot_budget[slot] = 0
                    self._maybe_finish(slot, -1)
            # prune marks for ids this engine never saw (e.g. a failed
            # submit still cancels in the router's cleanup path)
            cutoff = time.monotonic() - 600.0
            with self._done_lock:
                for rid, t in list(self._cancelled.items()):
                    if t < cutoff:
                        del self._cancelled[rid]
        with tracing.span("rtpu.engine.admit") as sp:
            self._admit()
        self._admit_ns += sp.dur_ns
        with tracing.span("rtpu.engine.dispatch") as sp:
            dispatched = self._dispatch(np, jnp)
        self._dispatch_ns += sp.dur_ns
        self._ticks += 1
        self._inflight_depth_sum += len(self._inflight)
        self._slot_ticks_occupied += len(self._slot_req)
        self._slot_ticks_drained += sum(
            1 for s in self._slot_req
            if self._sched[s] >= self._slot_budget[s])
        # keep at most `depth` records in flight; when nothing was
        # dispatched, drain the pipeline so finished slots free up
        if self._inflight and (len(self._inflight) > self._depth
                               or not dispatched):
            with tracing.span("rtpu.engine.reap"):
                self._reap(np)
        if not dispatched and not self._inflight:
            if self._in.empty():
                with tracing.span("rtpu.engine.sleep") as sp:
                    time.sleep(0.002)
                self._sleep_ns += sp.dur_ns
