"""The engine the serving cells deploy: ``PagedLLMEngine`` plus the two
calls a profiler needs, because only the process that holds the chip can
trace it, a reference check, because only that process holds the
weights, and two read-only reports. Nothing of the engine's behaviour is changed. (A trace hook on
the engine itself would let this class go: PERF.md, Open questions.)
"""

from __future__ import annotations

from typing import Any, Dict, List

from ray_tpu.serve.paged_engine import PagedLLMEngine


class TracedPagedEngine(PagedLLMEngine):
    # Everything slow runs as a job on a thread of its own and is polled:
    # a call that held the replica's request thread for longer than the
    # controller's 10 s health ping would get the replica killed.

    def job_start(self, kind: str, *args) -> bool:
        import threading

        self._job_result: Any = None
        fn = {"start_trace": self._start_trace,
              "stop_trace": self._stop_trace,
              "reference_check": self._reference_check}[kind]

        def work() -> None:
            try:
                self._job_result = {"ok": fn(*args)}
            except Exception as e:  # noqa: BLE001 — reported to the caller
                self._job_result = {"error": repr(e)}

        threading.Thread(target=work, daemon=True,
                         name=f"bench-{kind}").start()
        return True

    def job_poll(self) -> Any:
        return self._job_result

    def _start_trace(self, trace_dir: str) -> bool:
        import jax

        jax.profiler.start_trace(trace_dir)
        return True

    def _stop_trace(self) -> bool:
        import jax

        jax.profiler.stop_trace()
        return True

    def _reference_check(self, prompt: List[int], produced: List[int],
                         pad_to: int) -> Dict[str, float]:
        """Teacher-forced plain float32 forward over prompt + produced,
        with this engine's own weights; see references/llama_ref.py."""
        from benchmark.references import llama_ref

        return llama_ref.greedy_regret(self._cfg, self._params, prompt,
                                       produced, pad_to)

    def device_report(self) -> Dict[str, Any]:
        import jax

        devs = jax.devices()
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devs]
        return {"platform": devs[0].platform,
                "device_kind": devs[0].device_kind,
                "device_count": len(devs), "memory_peak_bytes": peaks}

    def compile_count(self) -> int:
        """Programs this process has compiled or fetched from the cache
        so far (jit cache misses); the cell reads it at the window's two
        ends."""
        from benchmark.lib import compile_counter

        return compile_counter.count()
