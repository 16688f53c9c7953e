"""DataParallelTrainer: SPMD train loop over a worker gang.

Reference: python/ray/train/data_parallel_trainer.py:25 +
base_trainer.py:567 (fit). Differences by design: fit() drives the gang
directly (Tune wraps trainers at its own layer, rather than every fit being
a Tune trial), and the data-parallel substrate is a JAX mesh, not a torch
process group.

Fault tolerance: FailureConfig(max_failures) — on worker death or loop
error the gang is torn down, rebuilt, and restarted from the latest
persisted checkpoint (reference semantics).
"""

from __future__ import annotations

import json
import logging
import os
from typing import Any, Callable, Dict, Optional

from ray_tpu.train.backend import BackendConfig, JaxConfig
from ray_tpu.train.backend_executor import BackendExecutor, TrainingWorkerError
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.checkpoint_manager import CheckpointManager
from ray_tpu.train.config import RunConfig, ScalingConfig
from ray_tpu.train.result import Result
from ray_tpu.train.session import PreemptedError
from ray_tpu.train.storage import StorageContext
from ray_tpu.util import tracing

logger = logging.getLogger(__name__)


class DataParallelTrainer:
    def __init__(self,
                 train_loop_per_worker: Callable,
                 *,
                 train_loop_config: Optional[Dict[str, Any]] = None,
                 backend_config: Optional[BackendConfig] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 datasets: Optional[Dict[str, Any]] = None,
                 resume_from_checkpoint: Optional[Checkpoint] = None,
                 metadata: Optional[Dict[str, Any]] = None):
        self.train_loop_per_worker = train_loop_per_worker
        self.train_loop_config = train_loop_config or {}
        self.backend_config = backend_config or BackendConfig()
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.datasets = datasets or {}
        self.resume_from_checkpoint = resume_from_checkpoint
        self.metadata = metadata or {}

    # ----------------------------------------------------------------- fit
    def fit(self) -> Result:
        storage = StorageContext(self.run_config.resolved_storage_path(),
                                 experiment_name=self.run_config.name)
        storage.ensure_trial_dir()
        ckpt_mgr = CheckpointManager(storage,
                                     self.run_config.checkpoint_config)
        max_failures = self.run_config.failure_config.max_failures
        failures = 0
        preemptions = 0
        latest_metrics: Dict[str, Any] = {}
        history: list = []
        elastic_stats: list = []
        worker_spans: list = []
        last_error: Optional[BaseException] = None

        while True:
            executor = BackendExecutor(self.backend_config, self.scaling_config)
            try:
                # until the train function has been entered on every
                # worker: what a run pays before its first step
                with tracing.span("rtpu.train.start", keep=True,
                                  id=storage.trial_name):
                    executor.start()
                    # resume from the newest CONSISTENT checkpoint: torn/
                    # partial dirs (worker died mid-persist) are dropped
                    # with a warning instead of crashing the restart
                    resume = ckpt_mgr.latest_consistent() \
                        or self.resume_from_checkpoint
                    executor.start_training(
                        self.train_loop_per_worker,
                        self.train_loop_config,
                        context_kwargs={
                            "trial_name": storage.trial_name,
                            "experiment_name": storage.experiment_name,
                            "trial_dir": storage.trial_path,
                            "metadata": self.metadata,
                        },
                        checkpoint_path=resume.path if resume else None,
                        dataset_shards=self._shard_datasets(
                            self.scaling_config.num_workers),
                        storage_info={
                            "storage_path":
                                self.run_config.resolved_storage_path(),
                            "experiment_name": storage.experiment_name,
                            "trial_name": storage.trial_name,
                            "checkpoint_index_start": ckpt_mgr.next_index,
                        },
                        shard_fn=self._shard_datasets,
                    )
                while True:
                    results = executor.get_next_results()
                    if results is None:
                        break
                    # rank-0 metrics are the canonical row (reference keeps
                    # per-rank results but reports rank 0 by default)
                    latest_metrics = results[0].metrics
                    history.append(latest_metrics)
                    ckpt_dirs = [r.checkpoint_dir for r in results
                                 if r.checkpoint_dir]
                    if ckpt_dirs:
                        ckpt_mgr.register_persisted(ckpt_dirs[0], latest_metrics)
                # the gang that finished the run; one that failed is not
                # asked (its workers are dead or wedged in a collective)
                worker_spans.extend(executor.collect_spans())
                last_error = None
                break
            # rtpu-lint: disable=L4 — this handler IS the restart
            # machinery: the enclosing while-loop rebuilds the gang and
            # resumes from the latest consistent checkpoint (bounded by
            # max_failures / max_preemptions)
            except TrainingWorkerError as e:
                last_error = e
                if isinstance(e.__cause__, PreemptedError):
                    # scheduled eviction, not a fault: restart from the
                    # latest checkpoint without consuming max_failures
                    preemptions += 1
                    logger.warning(
                        "gang preempted (%d/%d); restarting from latest "
                        "checkpoint", preemptions,
                        self.run_config.failure_config.max_preemptions)
                    if preemptions > \
                            self.run_config.failure_config.max_preemptions:
                        break
                else:
                    failures += 1
                    logger.warning("training failed (%d/%d): %s",
                                   failures, max_failures, e)
                    if max_failures >= 0 and failures > max_failures:
                        break
            finally:
                elastic_stats.extend(executor.elastic_stats)
                with tracing.span("rtpu.train.shutdown", keep=True,
                                  id=storage.trial_name):
                    executor.shutdown()

        # the run's spans, the driver's and every worker's, on one wall
        # clock: kept in memory while it ran, written once it has ended
        with storage.fs.open(os.path.join(storage.experiment_path,
                                          "trace_spans.json"), "w") as f:
            json.dump(sorted(tracing.chrome_events() + worker_spans,
                             key=lambda e: e["ts"]), f)
        return Result(metrics=latest_metrics,
                      checkpoint=ckpt_mgr.best,
                      error=last_error,
                      path=storage.trial_path,
                      metrics_history=history,
                      elastic_stats=elastic_stats)

    # ------------------------------------------------------------ datasets
    def _shard_datasets(self, n: int):
        if not self.datasets:
            return None
        shards = [dict() for _ in range(n)]
        for name, ds in self.datasets.items():
            split = getattr(ds, "streaming_split", None)
            if callable(split):
                for rank, piece in enumerate(split(n, equal=True)):
                    shards[rank][name] = piece
            else:
                for rank in range(n):
                    shards[rank][name] = ds
        return shards


class JaxTrainer(DataParallelTrainer):
    """DataParallelTrainer preconfigured with the JAX backend
    (the analogue of the reference's TorchTrainer, train/torch/config.py:154,
    with the mesh in place of a NCCL process group)."""

    def __init__(self, train_loop_per_worker: Callable, *,
                 jax_config: Optional[JaxConfig] = None, **kwargs):
        kwargs.setdefault("backend_config", jax_config or JaxConfig())
        super().__init__(train_loop_per_worker, **kwargs)
