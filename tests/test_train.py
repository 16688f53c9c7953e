"""Train library tests: session/report pump, gang orchestration,
checkpointing + retention, fault-tolerant restart, JAX data-parallel e2e.

Reference analogues: python/ray/train/tests/test_data_parallel_trainer.py,
test_backend.py, test_checkpoint_manager.py.
"""

import json
import os

import pytest

import ray_tpu
from ray_tpu import train
from ray_tpu.train import (
    CheckpointConfig,
    FailureConfig,
    JaxConfig,
    JaxTrainer,
    RunConfig,
    ScalingConfig,
)


@pytest.fixture()
def run_cfg(tmp_path):
    def make(**kw):
        kw.setdefault("storage_path", str(tmp_path / "results"))
        kw.setdefault("name", "exp")
        return RunConfig(**kw)

    return make


def test_single_worker_report(rt, run_cfg):
    def loop(config):
        for step in range(3):
            train.report({"step": step, "loss": 1.0 / (step + 1),
                          "lr": config["lr"]})

    trainer = train.DataParallelTrainer(
        loop, train_loop_config={"lr": 0.1},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=run_cfg())
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 2
    assert result.metrics["lr"] == 0.1
    assert len(result.metrics_history) == 3


def test_multi_worker_context_and_collective(rt, run_cfg):
    def loop(config):
        import numpy as np

        from ray_tpu.parallel import collective

        ctx = train.get_context()
        assert ctx.get_world_size() == 2
        total = collective.allreduce(
            np.array([float(ctx.get_world_rank() + 1)]), group_name="train")
        train.report({"rank": ctx.get_world_rank(),
                      "allreduced": float(total[0])})

    trainer = train.DataParallelTrainer(
        loop,
        backend_config=JaxConfig(platform=None, host_collectives=True),
        scaling_config=ScalingConfig(num_workers=2),
        run_config=run_cfg())
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["rank"] == 0
    assert result.metrics["allreduced"] == 3.0  # 1 + 2


def test_checkpointing_and_retention(rt, run_cfg, tmp_path):
    def loop(config):
        import tempfile

        for step in range(4):
            with tempfile.TemporaryDirectory() as d:
                with open(os.path.join(d, "state.json"), "w") as f:
                    json.dump({"step": step}, f)
                train.report({"step": step, "score": float(step)},
                             checkpoint=train.Checkpoint.from_directory(d))

    trainer = train.DataParallelTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=run_cfg(checkpoint_config=CheckpointConfig(
            num_to_keep=2, checkpoint_score_attribute="score")))
    result = trainer.fit()
    assert result.error is None
    # best checkpoint by score is the last one (score=3)
    with result.checkpoint.as_directory() as d:
        state = json.load(open(os.path.join(d, "state.json")))
    assert state["step"] == 3
    # retention: only 2 checkpoint dirs remain in the trial dir
    ckpts = [p for p in os.listdir(result.path) if p.startswith("checkpoint_")]
    assert len(ckpts) == 2


def test_failure_restart_resumes_from_checkpoint(rt, run_cfg, tmp_path):
    marker = tmp_path / "crashed_once"

    def loop(config):
        import tempfile

        ckpt = train.get_checkpoint()
        start = 0
        if ckpt is not None:
            with ckpt.as_directory() as d:
                start = json.load(open(os.path.join(d, "state.json")))["step"] + 1
        for step in range(start, 4):
            if step == 2 and not os.path.exists(config["marker"]):
                open(config["marker"], "w").close()
                raise RuntimeError("injected failure at step 2")
            with tempfile.TemporaryDirectory() as d:
                with open(os.path.join(d, "state.json"), "w") as f:
                    json.dump({"step": step, "resumed_from": start}, f)
                train.report({"step": step, "resumed_from": start},
                             checkpoint=train.Checkpoint.from_directory(d))

    trainer = train.DataParallelTrainer(
        loop, train_loop_config={"marker": str(marker)},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=run_cfg(failure_config=FailureConfig(max_failures=1)))
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 3
    # second attempt resumed from the checkpoint at step 1, not from scratch
    assert result.metrics["resumed_from"] == 2


def test_failure_exhausts_retries(rt, run_cfg):
    def loop(config):
        raise ValueError("always fails")

    trainer = train.DataParallelTrainer(
        loop, scaling_config=ScalingConfig(num_workers=1),
        run_config=run_cfg(failure_config=FailureConfig(max_failures=1)))
    result = trainer.fit()
    assert result.error is not None
    assert "always fails" in str(result.error)


def test_jax_trainer_data_parallel_sgd(rt, run_cfg):
    """End-to-end: 2 workers fit y = 2x by SGD, averaging grads across the
    gang via the host collective group (the DCN data-parallel path)."""

    def loop(config):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.parallel import collective

        ctx = train.get_context()
        rank, world = ctx.get_world_rank(), ctx.get_world_size()
        # per-rank disjoint data shard
        xs = jnp.arange(rank * 8, (rank + 1) * 8, dtype=jnp.float32)
        ys = 2.0 * xs

        def loss_fn(w):
            return jnp.mean((w * xs - ys) ** 2)

        grad_fn = jax.jit(jax.grad(loss_fn))
        w = jnp.float32(0.0)
        for step in range(30):
            g = grad_fn(w)
            g = collective.allreduce(np.asarray(g), group_name="train") / world
            w = w - 0.01 * jnp.asarray(g)
            train.report({"step": step, "w": float(w)})

    trainer = JaxTrainer(
        loop, scaling_config=ScalingConfig(num_workers=2),
        run_config=run_cfg())
    result = trainer.fit()
    assert result.error is None
    assert abs(result.metrics["w"] - 2.0) < 0.1


def test_uneven_reports_raise(rt, run_cfg):
    def loop(config):
        ctx = train.get_context()
        n = 2 if ctx.get_world_rank() == 0 else 1
        for step in range(n):
            train.report({"step": step})

    trainer = train.DataParallelTrainer(
        loop, scaling_config=ScalingConfig(num_workers=2),
        run_config=run_cfg())
    result = trainer.fit()
    assert result.error is not None


def test_dataset_ingest_streaming_split(rt, run_cfg):
    """Train<->Data integration: datasets shard to workers via
    streaming_split; each worker sees a disjoint, complete partition."""
    import ray_tpu.data as rd

    def loop(config):
        import numpy as np
        from ray_tpu.parallel import collective

        it = train.get_dataset_shard("train")
        seen = [int(r["id"]) for r in it.iter_rows()]
        # Aggregate across the gang: together the shards must cover the
        # range exactly once (no duplication, no drops).
        totals = collective.allreduce(
            np.asarray([len(seen), sum(seen)], np.float64),
            group_name="train")
        train.report({"n": int(totals[0]), "sum": int(totals[1]),
                      "mine": len(seen)})

    ds = rd.range(100, parallelism=8)
    trainer = JaxTrainer(
        loop, scaling_config=ScalingConfig(num_workers=2),
        datasets={"train": ds}, run_config=run_cfg())
    result = trainer.fit()
    assert result.error is None
    hist = result.metrics_history
    assert hist[-1]["n"] == 100
    assert hist[-1]["sum"] == sum(range(100))
    assert 0 < hist[-1]["mine"] < 100


def test_dataset_ingest_batches_to_jax(rt, run_cfg):
    import ray_tpu.data as rd
    import numpy as np

    def loop(config):
        it = train.get_dataset_shard("train")
        total = 0
        rows = 0
        for batch in it.iter_batches(batch_size=16, prefetch_batches=1):
            total += int(batch["id"].sum())
            rows += len(batch["id"])
        train.report({"rows": rows, "total": total})

    ds = rd.range(64, parallelism=4)
    trainer = train.DataParallelTrainer(
        loop, scaling_config=ScalingConfig(num_workers=2),
        datasets={"train": ds}, run_config=run_cfg())
    result = trainer.fit()
    assert result.error is None
    last = result.metrics_history[-1]
    assert last["rows"] > 0
    # rank-0's shard sums to a strict subset of the full range's sum
    assert 0 < last["total"] < sum(range(64))


def test_gpt2_language_model_training_e2e(rt, run_cfg):
    """The north-star "GPT-2-125M on wikitext-2" at tiny size: GPT-2
    language-model training on a Data-ingested synthetic corpus, 1 worker
    — loss must drop."""
    import ray_tpu.data as rd

    def loop(config):
        import jax
        import jax.numpy as jnp
        import numpy as np
        import optax

        from ray_tpu.models import gpt2

        cfg = gpt2.GPT2Config.tiny()
        params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
        tx = optax.adam(1e-3)
        opt = tx.init(params)

        def step(params, opt, tokens):
            loss, grads = jax.value_and_grad(
                lambda p: gpt2.loss_fn(cfg, p, {"tokens": tokens}))(params)
            upd, opt = tx.update(grads, opt, params)
            return optax.apply_updates(params, upd), opt, loss

        jstep = jax.jit(step)
        shard = train.get_dataset_shard("train")
        first = last = None
        for epoch in range(3):
            for batch in shard.iter_batches(batch_size=8,
                                            batch_format="numpy"):
                toks = jnp.asarray(np.stack(batch["tokens"]), jnp.int32)
                params, opt, loss = jstep(params, opt, toks)
                if first is None:
                    first = float(loss)
                last = float(loss)
        train.report({"first_loss": first, "last_loss": last})

    import numpy as np

    # learnable corpus: arithmetic token sequences (next token is a
    # deterministic function of the previous), unlike uniform noise whose
    # loss floor is log(vocab)
    corpus = [{"tokens": ((np.arange(33) * 3 + i) % 255).astype(np.int32)}
              for i in range(64)]
    trainer = JaxTrainer(
        loop, scaling_config=ScalingConfig(num_workers=1),
        datasets={"train": rd.from_items(corpus)},
        run_config=run_cfg())
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["last_loss"] < result.metrics["first_loss"] * 0.8


def test_orbax_sharded_checkpoint_reshard_restore():
    """Orbax save/restore (train/orbax_checkpoint.py): sharded arrays
    save per-shard and restore RESHARDED onto a different mesh — the
    property that makes elastic gang restarts cheap."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.train import orbax_checkpoint as oc

    mesh8 = build_mesh(MeshSpec({"fsdp": 8}))
    x = jax.device_put(jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                       NamedSharding(mesh8, P("fsdp", None)))
    with tempfile.TemporaryDirectory() as d:
        p = oc.save(os.path.join(d, "ck"), {"w": x, "step": jnp.int32(7)})
        mesh4 = build_mesh(MeshSpec({"fsdp": 4}),
                           devices=jax.devices()[:4])
        like = {"w": jax.ShapeDtypeStruct(
                    (8, 8), jnp.float32,
                    sharding=NamedSharding(mesh4, P("fsdp", None))),
                "step": jax.ShapeDtypeStruct((), jnp.int32)}
        out = oc.restore(p, like=like)
        assert np.array_equal(np.asarray(out["w"]), np.asarray(x))
        assert out["w"].sharding.mesh.shape["fsdp"] == 4
        assert int(out["step"]) == 7
