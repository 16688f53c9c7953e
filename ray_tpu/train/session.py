"""In-training-loop session: report/get_checkpoint/get_context.

Reference: python/ray/train/_internal/session.py (_TrainSession :111,
report :667, get_checkpoint :754). The user loop runs on a thread inside the
worker actor; ``report`` hands a result to the actor thread and blocks in
lockstep until the driver has consumed it — that keeps all workers advancing
step-for-step, which matters on TPU where every mesh member must enter the
same jitted collective program together.
"""

from __future__ import annotations

import os
import queue
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.pulse import LoopPulse
from ray_tpu.util import tracing

_session: Optional["_TrainSession"] = None
_session_lock = threading.Lock()


@dataclass
class TrainingResult:
    metrics: Dict[str, Any]
    checkpoint_dir: Optional[str] = None   # worker-local dir to persist
    done: bool = False
    error: Optional[BaseException] = None


@dataclass
class TrainContext:
    """What a worker knows about its place in the gang (reference:
    ray.train.get_context() → TrainContext)."""

    world_size: int = 1
    world_rank: int = 0
    local_rank: int = 0
    local_world_size: int = 1
    node_rank: int = 0
    trial_name: str = ""
    experiment_name: str = ""
    trial_dir: str = ""
    metadata: Dict[str, Any] = field(default_factory=dict)

    def get_world_size(self) -> int:
        return self.world_size

    def get_world_rank(self) -> int:
        return self.world_rank

    def get_local_rank(self) -> int:
        return self.local_rank

    def get_local_world_size(self) -> int:
        return self.local_world_size

    def get_node_rank(self) -> int:
        return self.node_rank

    def get_trial_name(self) -> str:
        return self.trial_name

    def get_experiment_name(self) -> str:
        return self.experiment_name

    def get_trial_dir(self) -> str:
        return self.trial_dir


# the counters a train loop may put into ``train.report``: the session
# serves the last value of each as ``rtpu_train_<key>``. Of routed experts
# (``moe_rows_held``: of the routed rows, those the experts held here
# multiplied, where a layer holds a share of its experts;
# ``moe_rows_passed``: the rows the passes over those took, padding and
# all, ``ops/moe.rows_passed``: held over passed is how full they were;
# ``moe_router_bias_abs_max``: the largest selection bias of a router
# that balances its load by one, ``stack.Stack.update_router_bias``); of
# selective-scan layers (``ssm_state_abs_max``: the largest ``|S|`` a scan
# layer's state holds after a sequence's last position,
# ``ops/ssm.mamba2_mixer``: a state that grows from step to step says the
# decays have drifted toward 1); of gated delta-rule layers
# (``gdn_state_abs_max``: the same of a linear layer's ``[value, key]``
# state, ``ops/delta.gated_delta_mixer``: with ``beta`` up to 2 a state's
# eigenvalue along a key may be negative, and a state that grows says the
# keys have lost their unit length or the decays their float32); of layers
# whose index chooses their keys (``dsa_pairs_chosen_share``: the pairs
# chosen over the causal pairs; ``dsa_index_loss``: the layers' sum of
# ``KL(p_t || softmax_{S_t} I)``, ``ops/mla.index_terms``: an index that
# stops learning to rank as the attention weighs shows here first); of a
# stack with a multi-token prediction module (``mtp_cross_entropy``: the
# module's cross entropy of the token after the next,
# ``stack.Stack.loss_terms``, beside the loss it is a tenth of); of Kimi
# Delta Attention layers (``kda_state_abs_max``: as ``gdn_state_abs_max``;
# ``kda_log_decay_min``: the smallest log decay a key channel of a step,
# ``ops/delta.kda_mixer``, which must stay above the gate's lower bound: at
# it a sub-block's decays leave float32). A new operator adds its
# counter's name here.
STEP_COUNTERS = ("moe_rows_routed", "moe_rows_held", "moe_rows_passed",
                 "moe_expert_load_max_over_mean", "moe_router_bias_abs_max",
                 "ssm_state_abs_max", "gdn_state_abs_max",
                 "dsa_pairs_chosen_share", "dsa_index_loss",
                 "mtp_cross_entropy", "kda_state_abs_max",
                 "kda_log_decay_min")


class SessionInterruptedError(BaseException):
    """Raised inside the user train loop when the driver interrupts the
    session (gang resize: a peer died or the gang is growing back). A
    BaseException on purpose: a user loop's ``except Exception`` must not
    swallow the interrupt — the loop is being unwound so the worker can
    rejoin at the new world size and resume from the last consistent
    checkpoint."""


class _TrainSession:
    """Pumps results from the user training thread to the actor thread.

    Checkpoint persistence happens HERE, worker-side, inside ``report`` —
    before the result is handed to the driver — because the worker-local
    checkpoint dir may be temporary and, on multi-node, not reachable from
    the driver at all (reference: storage upload in train/_internal/
    session.py report path).
    """

    def __init__(self, train_fn, config: Dict[str, Any], context: TrainContext,
                 checkpoint: Optional[Checkpoint] = None,
                 dataset_shards: Optional[Dict[str, Any]] = None,
                 storage=None, checkpoint_index_start: int = 0,
                 checkpoint_upload_rank: Optional[int] = 0):
        self.context = context
        self.loaded_checkpoint = checkpoint
        self.dataset_shards = dataset_shards or {}
        self.storage = storage
        self._ckpt_index = checkpoint_index_start
        self._ckpt_upload_rank = checkpoint_upload_rank
        self._result_q: "queue.Queue[TrainingResult]" = queue.Queue(maxsize=1)
        self._consumed = threading.Semaphore(0)
        self._finished = False
        self._interrupted: Optional[str] = None
        self._reports = 0
        # the last reported value of each of STEP_COUNTERS
        self._counters: Dict[str, float] = {}
        # times every wait of the loop's thread from beside it
        # (train/pulse.py); its counts are served with the session's
        self._pulse = pulse = LoopPulse(context.trial_name)
        import weakref

        from ray_tpu import metrics

        me = weakref.ref(self)     # the registry keeps no session alive
        metrics.REGISTRY.register_source("rtpu_train", lambda: {
            "reports": me()._reports, "checkpoints": me()._ckpt_index,
            "world_rank": me().context.world_rank, **me()._counters,
            "loop_waits": pulse.rhythm.main_waits,
            "loop_stalls": pulse.rhythm.stalls,
            "loop_stalled_seconds": pulse.rhythm.stalled_ns / 1e9,
            "proc_paused_seconds": pulse.rhythm.paused_ns / 1e9})

        def runner():
            error = None
            loop = tracing.span("rtpu.train.loop", id=context.trial_name,
                                keep=True)
            try:
                pulse.start()
                with loop:
                    try:
                        train_fn(config) if _wants_config(train_fn) \
                            else train_fn()
                    finally:
                        # the pulse has ended and the span is closed
                        # before the done sentinel is put: a driver that
                        # collects the gang's spans after it finds both
                        loop.attrs.update(pulse.stop())
            except BaseException as e:  # surfaced to the driver, not swallowed
                # Includes SessionInterruptedError: the queue may still
                # hold the result the interrupt overtook, but the driver
                # drains every queued result until it sees this done
                # sentinel, so the blocking put always completes — and
                # the sentinel is never dropped.
                error = e
            self._result_q.put(
                TrainingResult(metrics={}, done=True, error=error))

        self._thread = threading.Thread(target=runner, daemon=True,
                                        name="rtpu-train-loop")

    def start(self):
        self._thread.start()

    # --------------------------------------------- called by the driver
    # (via _TrainWorker.interrupt_session, on the actor's second
    # concurrency slot while next_result may be blocked on the first)
    def interrupt(self, reason: str = "gang resize"):
        """Ask the train loop to unwind at its next report boundary.

        Protocol: set the flag, then release one ``_consumed`` token so a
        loop blocked in lockstep (report() waiting for the driver) wakes
        up and observes the flag. A loop blocked inside a collective is
        unblocked separately by the coordinator abort. The driver must
        keep calling ``next_result`` (draining) until it sees a ``done``
        result — in-flight reports complete normally before the loop
        raises SessionInterruptedError."""
        self._interrupted = reason
        self._consumed.release()

    # ------------------------------------------------- called by train_fn
    def report(self, metrics: Dict[str, Any],
               checkpoint_dir: Optional[str] = None):
        if self._interrupted is not None:
            raise SessionInterruptedError(self._interrupted)
        persisted = None
        if checkpoint_dir is not None:
            if (self.storage is not None
                    and (self._ckpt_upload_rank is None
                         or self.context.world_rank == self._ckpt_upload_rank)):
                ckpt = self.storage.persist_checkpoint_dir(
                    checkpoint_dir, self._ckpt_index)
                persisted = ckpt.path
            self._ckpt_index += 1
        self._reports += 1
        self._counters.update({k: metrics[k] for k in STEP_COUNTERS
                               if isinstance(metrics.get(k), (int, float))})
        self._result_q.put(TrainingResult(metrics=dict(metrics),
                                          checkpoint_dir=persisted))
        # Lockstep: wait until the driver consumed this result before the
        # training loop continues (mirrors reference's blocking report).
        self._consumed.acquire()
        if self._interrupted is not None:
            raise SessionInterruptedError(self._interrupted)

    # --------------------------------------------------- called by driver
    def next_result(self, timeout: Optional[float] = None) -> TrainingResult:
        res = self._result_q.get(timeout=timeout)
        if res.done:
            self._finished = True
        else:
            self._consumed.release()
        return res

    def finished(self) -> bool:
        return self._finished


# ------------------------------------------------------------ public API

def _get_session() -> _TrainSession:
    if _session is None:
        raise RuntimeError(
            "No train session active — this API must be called inside a "
            "train_loop_per_worker launched by a Trainer.")
    return _session


def _set_session(s: Optional[_TrainSession]):
    global _session
    _session = s


# ---- preemption (TPU maintenance events arrive as SIGTERM) -----------------

_preempt_event = threading.Event()


class PreemptedError(RuntimeError):
    """Raised by a train loop that observed preemption (after saving its
    checkpoint). The trainer treats it as a gang-restart signal that does
    NOT consume the failure budget — preemptions are scheduled events,
    not faults (reference analogue: spot/maintenance handling in
    cluster autoscaling; TPU docs deliver maintenance events as SIGTERM
    with a grace window)."""


def _core_preempt_event():
    """The worker-process-level preemption flag, when running inside a
    runtime worker (set by the SIGTERM handler that trap_sigterm actors
    install at creation — see core/worker_proc.py). None driver-side."""
    from ray_tpu.core import runtime_context

    core = runtime_context.get_core_or_none()
    return getattr(core, "preempted", None)


def preempted() -> bool:
    """True once a preemption signal (SIGTERM) reached this worker.
    Poll at step boundaries: save a checkpoint, then raise
    PreemptedError so the gang restarts cleanly on fresh resources."""
    if _preempt_event.is_set():
        return True
    ev = _core_preempt_event()
    return ev is not None and ev.is_set()


def _flag_preemption():
    """Mark this worker preempted (what the SIGTERM handler does; also
    the hook for environments that deliver maintenance events through a
    channel other than signals)."""
    _preempt_event.set()


def _install_preemption_handler():
    """Worker-side: arm the SIGTERM→flag route for a (new or resized)
    gang incarnation. The actual signal handler lives in the worker
    process's main thread, installed at actor creation for trap_sigterm
    actors (core/worker_proc.py) — actor calls run on pool threads when
    max_concurrency > 1 and may not set signal handlers themselves, so
    this call only CLEARS stale flags: a preemption observed by a
    previous gang on a reused process must not re-fire, while a SIGTERM
    landing after this point must stick. In-process sessions (driver-
    side unit tests) get a best-effort direct install instead."""
    import signal

    _preempt_event.clear()
    ev = _core_preempt_event()
    if ev is not None:
        ev.clear()
    # Only the main thread may install handlers (CPython rule). On a
    # pool thread the process-level handler installed at actor creation
    # (core/worker_proc.py) owns the SIGTERM route — skip explicitly
    # rather than swallow the ValueError, which is how the original
    # never-armed bug stayed invisible.
    if threading.current_thread() is threading.main_thread():
        signal.signal(signal.SIGTERM, lambda signum, frame:
                      _flag_preemption())


def report(metrics: Dict[str, Any], checkpoint: Optional[Checkpoint] = None,
           *, checkpoint_dir: Optional[str] = None):
    """Report metrics (and optionally a just-written checkpoint dir) to the
    driver. Blocks until the driver has processed the result."""
    s = _get_session()
    if checkpoint is not None and checkpoint_dir is None:
        checkpoint_dir = checkpoint.path
    # persisting the checkpoint and waiting for the driver to take the
    # result: the time a train loop stands still in a report. A session's
    # first is kept: an ``rtpu.jax.compile`` after it is a recompile
    with tracing.span("rtpu.train.report", id=s.context.trial_name,
                      keep=s._reports == 0):
        s.report(metrics, checkpoint_dir=checkpoint_dir)


def get_checkpoint() -> Optional[Checkpoint]:
    """The latest persisted checkpoint to resume from (None on fresh start)."""
    return _get_session().loaded_checkpoint


def get_context() -> TrainContext:
    s = _session
    return s.context if s is not None else TrainContext()


def get_dataset_shard(name: str = "train"):
    """This worker's shard of a dataset passed to the Trainer
    (reference: ray.train.get_dataset_shard)."""
    return _get_session().dataset_shards.get(name)


def _wants_config(fn) -> bool:
    import inspect

    try:
        return len(inspect.signature(fn).parameters) >= 1
    except (TypeError, ValueError):
        return False
