"""Multi-process (multi-controller) gang training through Train + the
cluster plane: each gang worker is a separate OS process contributing its
local XLA devices to ONE global jax.distributed mesh, per-step gradient
reduction happens inside the jitted program via XLA collectives (Gloo on
CPU, ICI on TPU pods), and the gang survives a worker kill by restarting
from the latest checkpoint.

This is the reference's most-used path — process-group setup across a
worker gang (python/ray/train/torch/config.py:66,
python/ray/train/_internal/backend_executor.py:129) — done the JAX way:
multi-controller SPMD over a global mesh instead of a NCCL process group.
"""

import os

import pytest

import ray_tpu
from ray_tpu import train
from ray_tpu.train import (
    FailureConfig,
    JaxConfig,
    JaxTrainer,
    RunConfig,
    ScalingConfig,
)
from tests.conftest import own_cluster

N_PROCS = 2
DEVS_PER_PROC = 4


@pytest.fixture()
def run_cfg(tmp_path):
    def make(**kw):
        kw.setdefault("storage_path", str(tmp_path / "results"))
        kw.setdefault("name", "exp")
        return RunConfig(**kw)

    return make


def _fsdp_gang_loop(config):
    """Runs INSIDE each gang worker process. jax.distributed is already
    initialized by the Jax backend hooks; every worker sees the GLOBAL
    device set and executes the same SPMD program (multi-controller JAX).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshSpec, build_mesh, named_sharding
    from ray_tpu.parallel.sharding import shard_pytree_like

    ctx = train.get_context()
    rank = ctx.get_world_rank()
    world = ctx.get_world_size()

    n_local = jax.local_device_count()
    n_global = jax.device_count()
    assert n_global == world * n_local, (
        f"global mesh must span the gang: {n_global} != {world}x{n_local}")

    mesh = build_mesh(MeshSpec({"fsdp": n_global}))
    cfg = llama.LlamaConfig.tiny()

    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    param_sh = shard_pytree_like(llama.logical_axes_without_layer(cfg), mesh)
    params = jax.device_put(params, param_sh)
    tx = optax.adamw(1e-2, weight_decay=0.0)
    opt_state = tx.init(params)

    # resume: every rank reloads identical params/opt from the checkpoint
    start_step = 0
    ckpt = train.get_checkpoint()
    if ckpt is not None:
        import pickle

        with ckpt.as_directory() as d:
            with open(os.path.join(d, "state.pkl"), "rb") as f:
                state = pickle.load(f)
        start_step = state["step"] + 1
        params = jax.device_put(
            jax.tree.map(jnp.asarray, state["params"]), param_sh)
        opt_state = tx.init(params)

    batch_sh = named_sharding(mesh, "batch", None)
    global_batch, seq = 2 * n_global, 33

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: llama.loss_fn(cfg, p, {"tokens": tokens}, mesh=mesh)
        )(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    step_fn = jax.jit(train_step, donate_argnums=(0, 1))

    steps = int(config.get("steps", 6))
    fail_at = config.get("fail_at")
    rng = np.random.default_rng(7)  # same stream on all ranks
    for step in range(start_step, steps):
        host_tokens = rng.integers(
            0, cfg.vocab_size, (global_batch, seq)).astype(np.int32)
        # each process contributes the shards it owns of the global batch
        tokens = jax.make_array_from_callback(
            (global_batch, seq), batch_sh, lambda idx: host_tokens[idx])
        params, opt_state, loss = step_fn(params, opt_state, tokens)
        loss_val = float(jax.device_get(loss))  # cross-process sync point

        # checkpoint state must be host-resident and complete: allgather
        # the sharded params on EVERY rank (it is a collective), rank 0
        # persists them
        from jax.experimental import multihost_utils

        host_params = multihost_utils.process_allgather(params, tiled=True)

        if (fail_at is not None and step == fail_at and rank == 1
                and not os.path.exists(config["sentinel"])):
            # sentinel file: the REBUILT gang (fresh processes) must not
            # fail again
            with open(config["sentinel"], "w") as f:
                f.write("failed")
            os._exit(1)

        if rank == 0:
            import pickle
            import tempfile

            with tempfile.TemporaryDirectory() as d:
                with open(os.path.join(d, "state.pkl"), "wb") as f:
                    pickle.dump({"step": step, "params": host_params}, f)
                train.report({"step": step, "loss": loss_val,
                              "global_devices": n_global},
                             checkpoint=train.Checkpoint.from_directory(d))
        else:
            train.report({"step": step, "loss": loss_val,
                          "global_devices": n_global})


def _gang_config(**extra):
    return JaxConfig(platform="cpu", cpu_devices_per_worker=DEVS_PER_PROC,
                     distributed=True, host_collectives=False, **extra)


def test_multiproc_gang_fsdp_loss_decreases(rt, run_cfg):
    """2 processes x 4 virtual devices = one 8-device global FSDP mesh;
    per-step gradient collectives cross process boundaries; loss drops."""
    trainer = JaxTrainer(
        _fsdp_gang_loop,
        train_loop_config={"steps": 6},
        jax_config=_gang_config(),
        scaling_config=ScalingConfig(num_workers=N_PROCS),
        run_config=run_cfg())
    result = trainer.fit()
    assert result.error is None
    hist = result.metrics_history
    assert hist[0]["global_devices"] == N_PROCS * DEVS_PER_PROC
    assert hist[-1]["loss"] < hist[0]["loss"], (
        f"loss did not decrease: {hist[0]['loss']} -> {hist[-1]['loss']}")


def test_multiproc_gang_restart_from_checkpoint(rt, run_cfg, tmp_path):
    """Kill one gang worker mid-training: the whole gang is torn down,
    rebuilt (fresh processes re-join jax.distributed), and training resumes
    from the last persisted checkpoint, completing all steps."""
    sentinel = str(tmp_path / "failed-once")
    trainer = JaxTrainer(
        _fsdp_gang_loop,
        train_loop_config={"steps": 6, "fail_at": 3, "sentinel": sentinel},
        jax_config=_gang_config(),
        scaling_config=ScalingConfig(num_workers=N_PROCS),
        run_config=run_cfg(failure_config=FailureConfig(max_failures=1)),
    )
    result = trainer.fit()
    assert result.error is None
    assert os.path.exists(sentinel), "the injected failure never fired"
    steps = [row["step"] for row in result.metrics_history]
    assert steps[-1] == 5, f"training did not complete: {steps}"
    # the restarted gang resumed from step >= 3's checkpoint, not step 0
    assert result.metrics_history[-1]["loss"] < result.metrics_history[0]["loss"]


def _orbax_gang_loop(config):
    """Every rank collectively orbax-saves its SHARDS of the global FSDP
    params (no allgather, no host spike), then restores and verifies."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.sharding import shard_pytree_like
    from ray_tpu.train import orbax_checkpoint as oc

    ctx = train.get_context()
    mesh = build_mesh(MeshSpec({"fsdp": jax.device_count()}))
    cfg = llama.LlamaConfig.tiny()
    params = jax.device_put(
        llama.init_params(cfg, jax.random.PRNGKey(0)),
        shard_pytree_like(llama.logical_axes_without_layer(cfg), mesh))

    path = os.path.join(config["dir"], "gang-ck")
    oc.save(path, {"params": params})  # collective across the gang
    like = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=a.sharding), params)
    out = oc.restore(path, like={"params": like})
    err = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))),
        params, out["params"])))
    train.report({"rank": ctx.get_world_rank(), "restore_err": err})


def test_multiproc_gang_orbax_sharded_checkpoint(rt, run_cfg, tmp_path):
    """Distributed checkpointing the TPU-native way: each gang process
    writes only the shards IT owns (orbax multihost), restore reassembles
    the sharded pytree bit-exactly."""
    trainer = JaxTrainer(
        _orbax_gang_loop,
        train_loop_config={"dir": str(tmp_path)},
        jax_config=_gang_config(),
        scaling_config=ScalingConfig(num_workers=N_PROCS),
        run_config=run_cfg())
    result = trainer.fit()
    assert result.error is None
    assert all(row["restore_err"] == 0.0
               for row in result.metrics_history)


def _pp_train_loop(config):
    """Pipeline-parallel training through the Train session: a pp x dp
    mesh inside a gang worker, loss_fn_pp as the objective."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshSpec, build_mesh

    cfg = llama.LlamaConfig.tiny(num_layers=4)
    mesh = build_mesh(MeshSpec({"pp": 2, "dp": 2}))
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tx = optax.adamw(1e-2)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt, tokens):
        loss, grads = jax.value_and_grad(lambda p: llama.loss_fn_pp(
            cfg, p, {"tokens": tokens}, mesh, num_microbatches=4))(params)
        upd, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, upd), opt, loss

    # FIXED batch: memorization makes the loss decrease deterministic
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 17)),
                         jnp.int32)
    for i in range(int(config.get("steps", 5))):
        params, opt, loss = step(params, opt, tokens)
        train.report({"step": i, "loss": float(loss)})


def test_pipeline_parallel_through_train_api(rt, run_cfg):
    """The user-facing path: JaxTrainer worker builds a pp x dp mesh and
    trains with the GPipe program; loss decreases."""
    trainer = JaxTrainer(
        _pp_train_loop,
        train_loop_config={"steps": 5},
        jax_config=JaxConfig(platform="cpu", cpu_devices_per_worker=4),
        scaling_config=ScalingConfig(num_workers=1),
        run_config=run_cfg())
    result = trainer.fit()
    assert result.error is None
    hist = result.metrics_history
    assert hist[-1]["loss"] < hist[0]["loss"], hist


def test_multiproc_gang_through_cluster_plane(run_cfg):
    """The north-star path: gang workers are hosted by node-server
    processes of a real (local) cluster — scheduling, actor creation, and
    result plumbing all cross the RPC plane, and the JAX mesh crosses the
    node boundary."""
    with own_cluster(2, num_workers_per_node=1,
                     node_resources=[{"CPU": 2}, {"CPU": 2}]) as c:
        trainer = JaxTrainer(
            _fsdp_gang_loop,
            train_loop_config={"steps": 4},
            jax_config=_gang_config(),
            scaling_config=ScalingConfig(num_workers=N_PROCS,
                                         placement_strategy="SPREAD"),
            run_config=run_cfg())
        result = trainer.fit()
        assert result.error is None
        hist = result.metrics_history
        assert hist[0]["global_devices"] == N_PROCS * DEVS_PER_PROC
        assert hist[-1]["loss"] < hist[0]["loss"]


def _preemptible_gang_loop(config):
    """Like _fsdp_gang_loop but the failure is a PREEMPTION: rank 1
    receives SIGTERM (the TPU maintenance-event delivery) mid-run, the
    backend-installed handler converts it to a flag, and the loop raises
    train.PreemptedError at the next step boundary — after the step's
    checkpoint already persisted."""
    import os as _os
    import pickle
    import signal
    import tempfile

    import numpy as np

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshSpec, build_mesh, named_sharding
    from ray_tpu.parallel.sharding import shard_pytree_like

    ctx = train.get_context()
    rank = ctx.get_world_rank()
    mesh = build_mesh(MeshSpec({"fsdp": jax.device_count()}))
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    param_sh = shard_pytree_like(llama.logical_axes_without_layer(cfg), mesh)
    params = jax.device_put(params, param_sh)
    tx = optax.adamw(1e-2, weight_decay=0.0)
    opt_state = tx.init(params)

    start_step = 0
    ckpt = train.get_checkpoint()
    if ckpt is not None:
        with ckpt.as_directory() as d:
            with open(_os.path.join(d, "state.pkl"), "rb") as f:
                state = pickle.load(f)
        start_step = state["step"] + 1
        params = jax.device_put(
            jax.tree.map(jnp.asarray, state["params"]), param_sh)
        opt_state = tx.init(params)

    batch_sh = named_sharding(mesh, "batch", None)
    global_batch, seq = 2 * jax.device_count(), 33

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: llama.loss_fn(cfg, p, {"tokens": tokens}, mesh=mesh)
        )(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    step_fn = jax.jit(train_step, donate_argnums=(0, 1))
    rng = np.random.default_rng(7)
    for step in range(start_step, int(config["steps"])):
        # the maintenance event: observed at a step boundary, AFTER the
        # previous step's checkpoint persisted
        if train.preempted():
            raise train.PreemptedError(f"maintenance event at step {step}")
        host_tokens = rng.integers(
            0, cfg.vocab_size, (global_batch, seq)).astype(np.int32)
        tokens = jax.make_array_from_callback(
            (global_batch, seq), batch_sh, lambda idx: host_tokens[idx])
        params, opt_state, loss = step_fn(params, opt_state, tokens)
        loss_val = float(jax.device_get(loss))
        from jax.experimental import multihost_utils

        host_params = multihost_utils.process_allgather(params, tiled=True)

        if (step == int(config["preempt_at"]) and rank == 1
                and not _os.path.exists(config["sentinel"])):
            with open(config["sentinel"], "w") as f:
                f.write("preempted")
            _os.kill(_os.getpid(), signal.SIGTERM)  # delivery, not death

        if rank == 0:
            with tempfile.TemporaryDirectory() as d:
                with open(_os.path.join(d, "state.pkl"), "wb") as f:
                    pickle.dump({"step": step, "params": host_params}, f)
                train.report({"step": step, "loss": loss_val},
                             checkpoint=train.Checkpoint.from_directory(d))
        else:
            train.report({"step": step, "loss": loss_val})


def test_multiproc_gang_preemption_sigterm_resumes(rt, run_cfg, tmp_path):
    """SIGTERM mid-run = TPU maintenance event: the worker checkpoints at
    the boundary, raises PreemptedError, and the gang restarts and
    resumes WITHOUT consuming the failure budget (max_failures=0)."""
    sentinel = str(tmp_path / "preempted-once")
    trainer = JaxTrainer(
        _preemptible_gang_loop,
        train_loop_config={"steps": 6, "preempt_at": 2,
                           "sentinel": sentinel},
        jax_config=_gang_config(),
        scaling_config=ScalingConfig(num_workers=N_PROCS),
        # max_failures=0: ONLY the preemption path can restart the gang
        run_config=run_cfg(failure_config=FailureConfig(max_failures=0)),
    )
    result = trainer.fit()
    assert result.error is None, result.error
    assert os.path.exists(sentinel), "the preemption never fired"
    steps = [row["step"] for row in result.metrics_history]
    assert steps[-1] == 5, f"training did not complete: {steps}"
    # resumed from the step-2 checkpoint (not from scratch)
    assert 0 in steps and steps.count(0) == 1, steps
