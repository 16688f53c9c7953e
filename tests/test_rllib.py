"""RLlib-lite: env dynamics, learner update mechanics, and PPO-on-CartPole
convergence to >=450 (the verdict's acceptance bar; reference test model:
rllib/algorithms/ppo/tests/test_ppo.py learning tests).
"""

from __future__ import annotations

import os
import time

import numpy as np
import pytest

from ray_tpu.rllib.envs import CartPoleVec
from ray_tpu.rllib.learner import PPOLearner
from ray_tpu.rllib.rl_module import MLPModule, to_numpy
from tests.conftest import own_runtime


@pytest.fixture(scope="module")
def rl_ray():
    with own_runtime(3):
        yield


def test_cartpole_dynamics():
    env = CartPoleVec(4, seed=0)
    obs = env.reset()
    assert obs.shape == (4, 4) and np.abs(obs).max() <= 0.05
    total_done = 0
    for _ in range(400):
        obs, rew, term, trunc = env.step(np.zeros(4, np.int64))
        assert rew.shape == (4,) and (rew == 1.0).all()
        total_done += int((term | trunc).sum())
    # pushing left forever must topple the pole repeatedly (termination,
    # not time-limit truncation)
    assert total_done >= 4


def test_module_numpy_matches_jax():
    m = MLPModule(4, 2)
    params = m.init_params(0)
    obs = np.random.default_rng(0).normal(size=(16, 4)).astype(np.float32)
    import jax.numpy as jnp

    lj, vj = m.apply(params, jnp.asarray(obs))
    ln, vn = m.apply_np(to_numpy(params), obs)
    assert np.allclose(np.asarray(lj), ln, atol=1e-5)
    assert np.allclose(np.asarray(vj), vn, atol=1e-5)


def test_learner_update_improves_objective():
    m = MLPModule(4, 2)
    learner = PPOLearner(m, num_epochs=2, minibatch_size=64)
    rng = np.random.default_rng(0)
    n = 256
    batch = {
        "obs": rng.normal(size=(n, 4)).astype(np.float32),
        "actions": rng.integers(0, 2, size=n).astype(np.int32),
        "logp_old": np.full(n, -0.7, np.float32),
        "advantages": rng.normal(size=n).astype(np.float32),
        "returns": rng.normal(size=n).astype(np.float32),
    }
    metrics = learner.update(batch)
    assert set(metrics) == {"pg_loss", "vf_loss", "entropy"}
    assert np.isfinite(list(metrics.values())).all()


def test_catch_pixels_env_dynamics():
    from ray_tpu.rllib.envs import CatchPixelsVec

    env = CatchPixelsVec(4, seed=0)
    obs = env.reset()
    assert obs.shape == (4, 100)
    assert env.obs_shape == (10, 10, 1)
    # ball pixel (1.0) and 3-wide paddle (0.5) are rendered
    assert (obs == 1.0).sum(axis=1).tolist() == [1, 1, 1, 1]
    assert (obs == 0.5).sum(axis=1).tolist() == [3, 3, 3, 3]
    total, done_count = 0.0, 0
    for _ in range(9 * 5):
        obs, rew, term, trunc = env.step(
            np.random.default_rng(1).integers(0, 3, 4))
        total += rew.sum()
        done_count += int(term.sum())
    assert done_count == 4 * 5  # episodes are exactly GRID-1 steps


def test_cnn_module_mesh_shardable():
    """The conv module is one pure jax function: it jits over a dp mesh
    with the batch sharded across all 8 virtual devices (the learner can
    scale data-parallel without touching the module)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.rllib.rl_module import CNNModule

    mod = CNNModule(obs_shape=(10, 10, 1), num_actions=3)
    params = mod.init_params(0)
    mesh = build_mesh(MeshSpec({"dp": len(jax.devices())}))
    obs = jax.device_put(jnp.ones((16, 100), jnp.float32),
                         NamedSharding(mesh, P("dp", None)))
    logits, value = jax.jit(mod.apply)(params, obs)
    assert logits.shape == (16, 3) and value.shape == (16,)


def test_ppo_cnn_learns_pixel_catch(rl_ray):
    """CNN RLModule + pixel env (the north-star PPO-on-Atari path, sans
    ALE): PPO with the conv encoder must go from random (~-0.3) to
    catching (>0.6) in CI minutes. Reference:
    rllib/core/models/torch/encoder.py:107 + ppo Atari configs."""
    from ray_tpu.rllib import PPOConfig

    algo = (PPOConfig()
            .environment("CatchPixels-v0")
            .env_runners(num_env_runners=2, num_envs_per_env_runner=16,
                         rollout_fragment_length=64)
            .training(lr=1e-3, gamma=0.99)
            .debugging(seed=0)
            .build())
    # the conv encoder actually engaged
    from ray_tpu.rllib.rl_module import CNNModule
    assert isinstance(algo.learner.module, CNNModule)
    try:
        best = -1.0
        for _ in range(40):
            result = algo.train()
            best = max(best, result["episode_return_mean"] or -1.0)
            if best >= 0.6:
                break
        assert best >= 0.6, f"pixel PPO failed to learn: best={best}"
    finally:
        algo.stop()


def test_impala_cnn_learns_pixel_catch(rl_ray):
    """IMPALA (async actor-learner, V-trace) with the conv encoder on the
    pixel env."""
    from ray_tpu.rllib import IMPALAConfig

    algo = (IMPALAConfig()
            .environment("CatchPixels-v0")
            .env_runners(num_env_runners=2, num_envs_per_env_runner=16,
                         rollout_fragment_length=32)
            .training(lr=1e-3, gamma=0.99)
            .debugging(seed=0)
            .build())
    try:
        best = -1.0
        for _ in range(60):
            result = algo.train()
            best = max(best, result.get("episode_return_mean") or -1.0)
            if best >= 0.5:
                break
        assert best >= 0.5, f"pixel IMPALA failed to learn: best={best}"
    finally:
        algo.stop()


def test_ppo_cartpole_reaches_450(rl_ray):
    from ray_tpu.rllib import PPOConfig

    algo = (PPOConfig()
            .environment("CartPole-v1")
            .env_runners(num_env_runners=2, num_envs_per_env_runner=8,
                         rollout_fragment_length=128)
            .training(lr=3e-4, gamma=0.99)
            .debugging(seed=0)
            .build())
    try:
        best_eval = 0.0
        for i in range(300):
            result = algo.train()
            # greedy eval once the stochastic mean is close (the greedy
            # policy typically clears 500 well before the sampled mean)
            if result["episode_return_mean"] >= 380 and i >= 10:
                best_eval = max(best_eval, algo.evaluate(num_episodes=8))
                if best_eval >= 450:
                    break
        assert best_eval >= 450, (
            f"PPO did not reach 450 (last mean "
            f"{result['episode_return_mean']:.1f}, eval {best_eval:.1f})")
    finally:
        algo.stop()


# ---------------------------------------------------------------------------
# round 2: off-policy families (DQN/SAC), IMPALA, replay, offline RL
# ---------------------------------------------------------------------------


def test_pendulum_dynamics():
    from ray_tpu.rllib.envs import PendulumVec

    env = PendulumVec(4, seed=0)
    obs = env.reset()
    assert obs.shape == (4, 3)
    # cos^2 + sin^2 == 1
    assert np.allclose(obs[:, 0] ** 2 + obs[:, 1] ** 2, 1.0, atol=1e-5)
    total = np.zeros(4)
    for _ in range(200):
        obs, rew, term, trunc = env.step(np.zeros((4, 1), np.float32))
        assert (rew <= 0).all() and not term.any()
        total += rew
    assert trunc.all()  # fixed 200-step episodes (truncation, no terminal)
    # hanging uncontrolled can't be near-optimal
    assert total.mean() < -500


def test_replay_buffer_ring_and_sampling():
    from ray_tpu.rllib import ReplayBuffer

    buf = ReplayBuffer(capacity=100, seed=0)
    for start in range(0, 250, 50):
        buf.add_batch({"x": np.arange(start, start + 50, dtype=np.int64)})
    assert len(buf) == 100
    sample = buf.sample(64)
    # ring holds only the newest 100 entries
    assert sample["x"].min() >= 150
    stacked = buf.sample_many(4, 32)
    assert stacked["x"].shape == (4, 32)


def test_prioritized_replay_prefers_high_td():
    from ray_tpu.rllib import PrioritizedReplayBuffer

    buf = PrioritizedReplayBuffer(capacity=100, alpha=1.0, seed=0)
    buf.add_batch({"x": np.arange(100, dtype=np.int64)})
    # item 7 gets 100x the priority of everything else
    prios = np.ones(100)
    prios[7] = 100.0
    buf.update_priorities(np.arange(100), prios)
    s = buf.sample_many(1, 512)
    frac_7 = (s["x"] == 7).mean()
    assert frac_7 > 0.2  # ~100/199 expected
    assert s["weights"].min() > 0 and s["weights"].max() <= 1.0


def test_vtrace_matches_numpy_reference():
    """Learner's scan-based V-trace vs a direct numpy recursion, on a
    boundary-free trajectory with a single bootstrap (the textbook
    Espeholt et al. 2018 setting)."""
    from ray_tpu.rllib.impala import ImpalaLearner
    from ray_tpu.rllib.rl_module import MLPModule

    rng = np.random.default_rng(0)
    T, N = 7, 3
    gamma = 0.99
    target_logp = rng.normal(size=(T, N)).astype(np.float32) * 0.3
    behavior_logp = rng.normal(size=(T, N)).astype(np.float32) * 0.3
    values = rng.normal(size=(T, N)).astype(np.float32)
    bootstrap = rng.normal(size=N).astype(np.float32)
    rewards = rng.normal(size=(T, N)).astype(np.float32)
    # no episode boundaries: next value IS values[t+1], bootstrap at T
    next_values = np.concatenate([values[1:], bootstrap[None]], axis=0)
    disc_boot = np.full((T, N), gamma, np.float32)
    cont = np.ones((T, N), np.float32)

    learner = ImpalaLearner(MLPModule(4, 2), gamma=gamma,
                            rho_bar=1.0, c_bar=1.0)
    import jax.numpy as jnp

    vs, pg_adv = learner._vtrace(
        jnp.asarray(target_logp), jnp.asarray(behavior_logp),
        jnp.asarray(values), jnp.asarray(next_values),
        jnp.asarray(rewards), jnp.asarray(disc_boot), jnp.asarray(cont))
    vs, pg_adv = np.asarray(vs), np.asarray(pg_adv)

    # numpy recursion (Espeholt et al. 2018, eq. 1)
    rho = np.minimum(1.0, np.exp(target_logp - behavior_logp))
    c = np.minimum(1.0, np.exp(target_logp - behavior_logp))
    deltas = rho * (rewards + gamma * next_values - values)
    vs_ref = np.zeros((T + 1, N), np.float32)
    vs_ref[T] = bootstrap
    acc = np.zeros(N, np.float32)
    for t in reversed(range(T)):
        acc = deltas[t] + gamma * c[t] * acc
        vs_ref[t] = values[t] + acc
    adv_ref = rho * (rewards + gamma * vs_ref[1:] - values)

    assert np.allclose(vs, vs_ref[:T], atol=1e-4)
    assert np.allclose(pg_adv, adv_ref, atol=1e-4)


def test_vtrace_truncation_bootstraps():
    """At a time-limit truncation the v_s target must bootstrap from
    V(final_obs), not treat the state as terminal."""
    from ray_tpu.rllib.impala import ImpalaLearner
    from ray_tpu.rllib.rl_module import MLPModule
    import jax.numpy as jnp

    T, N = 3, 1
    gamma = 0.9
    # on-policy (rho = c = 1), constant reward 1, truncation at t=1
    zeros = np.zeros((T, N), np.float32)
    values = np.asarray([[1.0], [2.0], [3.0]], np.float32)
    next_values = np.asarray([[2.0], [10.0], [4.0]], np.float32)
    rewards = np.ones((T, N), np.float32)
    terminated = zeros.copy()
    dones = zeros.copy()
    dones[1] = 1.0   # truncated (not terminated) at t=1
    disc_boot = gamma * (1.0 - terminated)
    cont = 1.0 - dones

    learner = ImpalaLearner(MLPModule(4, 2), gamma=gamma)
    vs, _ = learner._vtrace(
        jnp.asarray(zeros), jnp.asarray(zeros), jnp.asarray(values),
        jnp.asarray(next_values), jnp.asarray(rewards),
        jnp.asarray(disc_boot), jnp.asarray(cont))
    vs = np.asarray(vs)
    # t=2: vs = r + gamma * V(next) = 1 + 0.9*4 = 4.6
    assert np.isclose(vs[2, 0], 4.6, atol=1e-5)
    # t=1 (truncated): bootstraps from V(final_obs)=10 -> 1 + 9 = 10,
    # and the recursion does NOT leak t=2's delta across the boundary
    assert np.isclose(vs[1, 0], 1 + gamma * 10.0, atol=1e-5)
    # t=0: continues into t=1: delta0 + gamma*(vs1 - v1) + v0
    delta0 = 1 + gamma * 2.0 - 1.0
    assert np.isclose(vs[0, 0], 1.0 + delta0 + gamma * (10.0 - 2.0),
                      atol=1e-4)


def test_dqn_cartpole_learns(rl_ray):
    from ray_tpu.rllib import DQNConfig

    cfg = (DQNConfig()
           .environment("CartPole-v1")
           .env_runners(num_env_runners=2, num_envs_per_env_runner=8,
                        rollout_fragment_length=32)
           .training(lr=5e-4, gamma=0.99)
           .debugging(seed=2))
    cfg.train_kwargs.update(updates_per_iter=32, tau=0.005,
                            epsilon_decay_steps=20_000)
    algo = cfg.build()
    try:
        best = 0.0
        for i in range(300):
            r = algo.train()
            if i % 10 == 9 and r["episode_return_mean"] > 100:
                best = max(best, algo.evaluate(8))
                if best >= 400:
                    break
        assert best >= 400, f"DQN best eval {best:.1f}"
    finally:
        algo.stop()


def test_dqn_prioritized_replay_runs(rl_ray):
    from ray_tpu.rllib import DQNConfig

    cfg = (DQNConfig().environment("CartPole-v1")
           .env_runners(num_env_runners=1, num_envs_per_env_runner=4,
                        rollout_fragment_length=64)
           .debugging(seed=0))
    cfg.train_kwargs.update(prioritized_replay=True, learning_starts=256,
                            updates_per_iter=4)
    algo = cfg.build()
    try:
        for _ in range(4):
            r = algo.train()
        assert np.isfinite(r["loss"])
        assert r["num_env_steps_sampled"] == 4 * 64 * 4
    finally:
        algo.stop()


def test_impala_cartpole_learns(rl_ray):
    from ray_tpu.rllib import IMPALAConfig

    algo = (IMPALAConfig()
            .environment("CartPole-v1")
            .env_runners(num_env_runners=2, num_envs_per_env_runner=8,
                         rollout_fragment_length=40)
            .training(lr=6e-4, gamma=0.99)
            .debugging(seed=0)
            .build())
    try:
        best = 0.0
        for i in range(300):
            r = algo.train()
            if i % 20 == 19:
                best = max(best, algo.evaluate(8))
                if best >= 450:
                    break
        assert best >= 450, f"IMPALA best eval {best:.1f}"
    finally:
        algo.stop()


def test_sac_pendulum_learns(rl_ray):
    from ray_tpu.rllib import SACConfig

    cfg = (SACConfig()
           .environment("Pendulum-v1")
           .env_runners(num_env_runners=2, num_envs_per_env_runner=8,
                        rollout_fragment_length=16)
           .training(lr=3e-4, gamma=0.99)
           .debugging(seed=0))
    cfg.train_kwargs.update(updates_per_iter=256)
    algo = cfg.build()
    try:
        best = -1e9
        for i in range(150):
            r = algo.train()
            if i % 5 == 4:   # an evaluation costs a fiftieth of the five
                best = max(best, algo.evaluate(8))
                if best >= -300:
                    break
        assert best >= -300, f"SAC best eval {best:.1f}"
    finally:
        algo.stop()


def _expert_cartpole_data(num_steps: int = 1500, n_envs: int = 8):
    """Transitions from the classic linear CartPole expert."""
    from ray_tpu.rllib.envs import CartPoleVec

    env = CartPoleVec(n_envs, seed=3)
    obs = env.reset()
    rows = {"obs": [], "actions": [], "rewards": [], "next_obs": [],
            "dones": []}
    for _ in range(num_steps):
        a = (obs[:, 2] + obs[:, 3] > 0).astype(np.int32)
        nxt, rew, term, trunc = env.step(a)
        rows["obs"].append(obs.copy())
        rows["actions"].append(a)
        rows["rewards"].append(rew)
        rows["next_obs"].append(nxt.copy())
        rows["dones"].append(term.astype(np.float32))
        obs = nxt
    return {k: np.concatenate(v) if v[0].ndim > 1 else np.stack(v).reshape(-1)
            for k, v in ((k, vs) for k, vs in rows.items())}


def _greedy_cartpole_return(module, weights, episodes: int = 8) -> float:
    from ray_tpu.rllib.envs import CartPoleVec

    env = CartPoleVec(episodes, seed=11)
    obs = env.reset()
    total = np.zeros(episodes)
    finished = np.zeros(episodes, bool)
    for _ in range(501):
        out = module.apply_np(weights, obs)
        logits = out[0] if isinstance(out, tuple) else out
        obs, rew, term, trunc = env.step(np.argmax(logits, axis=-1))
        total += rew * (~finished)
        finished |= term | trunc
        if finished.all():
            break
    return float(total.mean())


def test_bc_clones_expert_from_dataset(rl_ray):
    from ray_tpu import data as rdata
    from ray_tpu.data.block import BlockAccessor
    from ray_tpu.rllib import BCLearner, MLPModule
    from ray_tpu.rllib.offline import train_offline

    cols = _expert_cartpole_data()
    block = BlockAccessor.batch_to_block(
        {"obs": cols["obs"], "actions": cols["actions"]})
    ds = rdata.from_blocks([block])

    module = MLPModule(4, 2, hidden=(64, 64))
    learner = BCLearner(module, lr=1e-3)
    loss = train_offline(learner, ds, num_epochs=8, batch_size=256)
    assert np.isfinite(loss)
    ret = _greedy_cartpole_return(module, learner.get_weights())
    assert ret >= 400, f"BC policy return {ret:.1f}"


def test_cql_conservative_gap_shrinks(rl_ray):
    from ray_tpu import data as rdata
    from ray_tpu.data.block import BlockAccessor
    from ray_tpu.rllib import CQLLearner, QMLPModule
    from ray_tpu.rllib.offline import train_offline
    import jax.numpy as jnp
    import jax

    cols = _expert_cartpole_data(num_steps=800)
    block = BlockAccessor.batch_to_block(cols)
    ds = rdata.from_blocks([block])

    module = QMLPModule(4, 2, hidden=(64, 64))
    learner = CQLLearner(module, lr=1e-3, alpha_cql=1.0)

    def gap(params):
        q = module.apply(params, jnp.asarray(cols["obs"][:512]))
        q_data = jnp.take_along_axis(
            q, jnp.asarray(cols["actions"][:512])[:, None], axis=-1)[:, 0]
        return float((jax.nn.logsumexp(q, axis=-1) - q_data).mean())

    before = gap(learner.params)
    loss = train_offline(learner, ds, num_epochs=6, batch_size=256,
                         shuffle=False)
    assert np.isfinite(loss)
    after = gap(learner.params)
    # the conservative penalty pushes Q(s, a_data) above OOD actions
    assert after < before


# ---------------------------------------------------------------------------
# multi-agent API (reference: rllib/env/multi_agent_env.py + policy map)
# ---------------------------------------------------------------------------


def test_multi_agent_env_dynamics():
    from ray_tpu.rllib.multi_agent import MultiAgentCoordination

    env = MultiAgentCoordination(4, seed=0)
    obs = env.reset()
    assert set(obs) == {"a0", "a1"}
    assert obs["a0"].shape == (4, env.obs_dim)
    same = {"a0": np.zeros(4, np.int64), "a1": np.zeros(4, np.int64)}
    obs, rew, term, trunc = env.step(same)
    assert (rew["a0"] == 1.0).all() and (rew["a1"] == 1.0).all()
    diff = {"a0": np.zeros(4, np.int64), "a1": np.ones(4, np.int64)}
    obs, rew, term, trunc = env.step(diff)
    assert (rew["a0"] == 0.0).all()
    truncated_seen = False
    for _ in range(env.episode_len):
        obs, rew, term, trunc = env.step(same)
        truncated_seen |= bool(trunc.any())
        assert not term.any()
    assert truncated_seen  # fixed-length episodes truncate, never terminate


def test_multi_agent_mapping_validation():
    from ray_tpu.rllib import MultiAgentPPOConfig

    cfg = MultiAgentPPOConfig().multi_agent(
        policies=["only"], policy_mapping_fn=lambda a: "nope")
    with pytest.raises(ValueError, match="unknown policies"):
        cfg.build()


def test_multi_agent_two_policies_learn_to_coordinate(rl_ray):
    from ray_tpu.rllib import MultiAgentPPOConfig

    cfg = (MultiAgentPPOConfig()
           .environment("Coordination-v0")
           .env_runners(num_env_runners=2, num_envs_per_env_runner=16,
                        rollout_fragment_length=32)
           .training(lr=3e-4, gamma=0.95)
           .debugging(seed=0)
           .multi_agent(policies=["p0", "p1"],
                        policy_mapping_fn=lambda a: ("p0" if a == "a0"
                                                     else "p1")))
    algo = cfg.build()
    try:
        best = 0.0
        for i in range(60):
            r = algo.train()
            if i % 10 == 9:
                best = max(best, algo.evaluate())
                if best >= 7.0:   # near-perfect: 8-step episodes, +1/step
                    break
        assert best >= 7.0, f"multi-agent eval {best:.2f}"
        # per-policy metrics are reported under a policy prefix
        assert any(k.startswith("p0/") for k in r)
        assert any(k.startswith("p1/") for k in r)
    finally:
        algo.stop()


def test_multi_agent_policies_to_train_freezes(rl_ray):
    from ray_tpu.rllib import MultiAgentPPOConfig

    cfg = (MultiAgentPPOConfig()
           .environment("Coordination-v0")
           .env_runners(num_env_runners=1, num_envs_per_env_runner=8,
                        rollout_fragment_length=16)
           .debugging(seed=0)
           .multi_agent(policies=["train_me", "frozen"],
                        policy_mapping_fn=lambda a: ("train_me"
                                                     if a == "a0"
                                                     else "frozen"),
                        policies_to_train=["train_me"]))
    algo = cfg.build()
    try:
        before = algo.learners["frozen"].get_weights()
        r = algo.train()
        after = algo.learners["frozen"].get_weights()
        flat_b = np.concatenate([w.ravel() for w in
                                 _tree_leaves(before)])
        flat_a = np.concatenate([w.ravel() for w in _tree_leaves(after)])
        np.testing.assert_array_equal(flat_b, flat_a)
        assert not any(k.startswith("frozen/") for k in r)
        assert any(k.startswith("train_me/") for k in r)
    finally:
        algo.stop()


def _tree_leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)


def test_appo_cartpole_reaches_450(rl_ray):
    """APPO (reference: rllib/algorithms/appo/appo.py:277): the IMPALA
    runner gang with a target-network V-trace clipped-surrogate learner
    must solve CartPole."""
    from ray_tpu.rllib import APPOConfig

    cfg = (APPOConfig()
           .environment("CartPole-v1")
           .env_runners(num_env_runners=2, num_envs_per_env_runner=16,
                        rollout_fragment_length=64)
           .training(lr=1e-3, gamma=0.99)
           .debugging(seed=0))
    cfg.train_kwargs["target_update_freq"] = 4
    algo = cfg.build()
    try:
        best_eval = 0.0
        for i in range(100):
            result = algo.train()
            # the greedy policy clears 450 well before the sampled mean
            # (same pattern as the PPO test): eval periodically
            if i >= 15 and i % 3 == 0:
                best_eval = max(best_eval, algo.evaluate(num_episodes=8))
                if best_eval >= 450:
                    break
        assert best_eval >= 450, (
            f"APPO did not reach 450 (last mean "
            f"{result['episode_return_mean']:.1f}, eval {best_eval:.1f})")
    finally:
        algo.stop()


def test_policy_server_external_client_process(rl_ray, tmp_path):
    """External-env policy serving (reference:
    rllib/env/policy_server_input.py + policy_client.py): a CLIENT
    PROCESS owns the environment and drives get_action/log_returns/
    end_episode over the RPC plane; the server-side trainer consumes the
    collected batches and pushes fresh weights; returns improve."""
    import subprocess
    import sys

    import numpy as np

    from ray_tpu.rllib.envs import make_env
    from ray_tpu.rllib.impala import ImpalaLearner
    from ray_tpu.rllib.policy_server import PolicyServerInput
    from ray_tpu.rllib.rl_module import build_pv_module

    probe = make_env("CartPole-v1", 1)
    spec = {"obs_dim": probe.obs_dim, "num_actions": probe.num_actions,
            "hidden": (64, 64)}
    srv = PolicyServerInput(spec, seed=0)
    learner = ImpalaLearner(build_pv_module(spec), lr=1e-3, gamma=0.99,
                            seed=0)
    # pre-compile the update: the first jit takes seconds, during which
    # a free-running client would finish before any weight refresh
    warm = {
        "obs": np.zeros((80, 1, spec["obs_dim"]), np.float32),
        "next_obs": np.zeros((80, 1, spec["obs_dim"]), np.float32),
        "actions": np.zeros((80, 1), np.int32),
        "behavior_logits": np.zeros((80, 1, spec["num_actions"]),
                                    np.float32),
        "rewards": np.zeros((80, 1), np.float32),
        "terminateds": np.zeros((80, 1), bool),
        "dones": np.zeros((80, 1), bool),
    }
    learner.update(warm)
    srv.set_weights(learner.get_weights())

    client_script = r"""
import sys
import numpy as np
from ray_tpu.rllib.envs import make_env
from ray_tpu.rllib.policy_server import PolicyClient

host, port, key_hex, episodes = sys.argv[1:5]
client = PolicyClient((host, int(port)), bytes.fromhex(key_hex))
env = make_env("CartPole-v1", 1, seed=1)
for _ in range(int(episodes)):
    obs = env.reset()
    eid = client.start_episode()
    while True:
        a = client.get_action(eid, obs[0])
        obs2, rew, term, trunc = env.step(np.array([a]))
        client.log_returns(eid, float(rew[0]))
        if term[0] or trunc[0]:
            client.end_episode(eid, obs2[0])
            break
        obs = obs2
print("CLIENT_DONE", flush=True)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-c", client_script, srv.address[0],
         str(srv.address[1]), srv.authkey.hex(), "300"],
        stdout=subprocess.PIPE, text=True, env=env)
    try:
        returns, updates = [], 0
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            b = srv.next_batch(80)
            if b is not None:
                learner.update(b)
                srv.set_weights(learner.get_weights())
                updates += 1
            elif proc.poll() is not None:
                break  # client done AND buffer drained
            else:
                time.sleep(0.02)
            returns.extend(srv.episode_returns())
        out, _ = proc.communicate(timeout=60)
        assert "CLIENT_DONE" in out
        assert updates >= 10, f"only {updates} learner updates"
        assert len(returns) >= 40, f"only {len(returns)} episodes"
        early = float(np.mean(returns[:10]))
        late = float(np.mean(returns[-10:]))
        assert late > early, (early, late)
        assert late > 40.0, (early, late)  # random CartPole is ~20
    finally:
        proc.kill()
        srv.close()


def test_model_catalog_space_dispatch():
    """The catalog (reference: rllib/models/catalog.py ModelCatalog)
    maps space pairs onto default modules, derives spaces from vec
    envs, and routes custom_model to a registered factory."""
    from ray_tpu.rllib import Box, Catalog, Discrete
    from ray_tpu.rllib.envs import make_env
    from ray_tpu.rllib.rl_module import (CNNModule, MLPModule,
                                         QMLPModule,
                                         SquashedGaussianModule,
                                         TwinQModule)

    m = Catalog.get_module(Box((4,)), Discrete(2))
    assert isinstance(m, MLPModule) and m.obs_dim == 4

    m = Catalog.get_module(Box((8, 8, 1)), Discrete(3))
    assert isinstance(m, CNNModule) and m.obs_shape == (8, 8, 1)

    m = Catalog.get_module(Box((3,)), Box((1,), low=-2.0, high=2.0))
    assert isinstance(m, SquashedGaussianModule)
    assert (m.action_low, m.action_high) == (-2.0, 2.0)

    assert isinstance(Catalog.get_q_module(Box((4,)), Discrete(2)),
                      QMLPModule)
    assert isinstance(Catalog.get_q_module(Box((3,)), Box((1,))),
                      TwinQModule)

    # spaces derive from the vec-env attribute convention
    obs, act = Catalog.spaces_of(make_env("CartPole-v1", 1))
    assert obs.shape == (4,) and isinstance(act, Discrete) and act.n == 2
    obs, act = Catalog.spaces_of(make_env("Pendulum-v1", 1))
    assert obs.shape == (3,) and isinstance(act, Box)
    obs, act = Catalog.spaces_of(make_env("CatchPixels-v0", 1))
    assert len(obs.shape) == 3 and obs.shape[-1] == 1

    # custom model registration wins over the defaults
    class Tiny(MLPModule):
        pass

    Catalog.register_custom_model(
        "tiny", lambda o, a, mc: Tiny(o.shape[0], a.n, hidden=(8,)))
    m = Catalog.get_module(Box((4,)), Discrete(2),
                           {"custom_model": "tiny"})
    assert isinstance(m, Tiny) and m.hidden == (8,)

    # a catalog-built module slots straight into a jitted forward
    m = Catalog.get_module(Box((4,)), Discrete(2))
    logits, v = m.apply_np(
        {k: _np_tree(v) for k, v in m.init_params(0).items()},
        np.zeros((5, 4), np.float32))
    assert logits.shape == (5, 2) and v.shape == (5,)


def _np_tree(x):
    import jax

    return jax.tree_util.tree_map(np.asarray, x)


def test_marwil_outweighs_bad_demonstrations(rl_ray):
    """MARWIL (reference: rllib/algorithms/marwil) weights imitation by
    exp(beta * advantage): trained on a 50/50 mix of expert and
    anti-expert demonstrations (with honest returns), it must recover
    the EXPERT policy, while plain BC on the same mix imitates the coin
    flip."""
    from ray_tpu import data as rdata
    from ray_tpu.data.block import BlockAccessor
    from ray_tpu.rllib import BCLearner, MARWILLearner, MLPModule
    from ray_tpu.rllib.offline import train_offline

    rng = np.random.default_rng(0)
    n = 2048
    obs = rng.normal(size=(n, 4)).astype(np.float32)
    expert_action = (obs[:, 0] + 0.5 * obs[:, 2] > 0).astype(np.int32)
    took_expert = rng.random(n) < 0.5
    actions = np.where(took_expert, expert_action, 1 - expert_action)
    # honest returns: expert actions pay off, mistakes don't
    returns = np.where(took_expert, 1.0, -1.0).astype(np.float32)
    returns += 0.1 * rng.normal(size=n).astype(np.float32)

    block = BlockAccessor.batch_to_block(
        {"obs": obs, "actions": actions, "returns": returns})
    ds = rdata.from_blocks([block])

    def greedy_accuracy(module, weights):
        logits, _ = module.apply_np(weights, obs)
        return float((np.argmax(logits, -1) == expert_action).mean())

    m_mod = MLPModule(4, 2, hidden=(64, 64))
    marwil = MARWILLearner(m_mod, lr=1e-2, beta=2.0)
    train_offline(marwil, ds, num_epochs=10, batch_size=256)
    marwil_acc = greedy_accuracy(m_mod, marwil.get_weights())

    b_mod = MLPModule(4, 2, hidden=(64, 64))
    bc = BCLearner(b_mod, lr=1e-3)
    train_offline(bc, ds, num_epochs=10, batch_size=256)
    bc_acc = greedy_accuracy(b_mod, bc.get_weights())

    assert marwil_acc > 0.9, f"MARWIL acc {marwil_acc:.2f}"
    # BC sees a 50/50 action mix per state: it cannot systematically
    # recover the expert
    assert marwil_acc > bc_acc + 0.2, (marwil_acc, bc_acc)


def test_offline_json_sample_batches_roundtrip(rl_ray, tmp_path):
    """Offline JSON format (reference: rllib/offline/json_reader.py):
    batches persist as JSON-lines and read back into a Dataset that
    drives an offline learner."""
    from ray_tpu.rllib import BCLearner, MLPModule
    from ray_tpu.rllib.offline import (read_sample_batch_json,
                                       train_offline,
                                       write_sample_batch_json)

    rng = np.random.default_rng(0)
    obs = rng.normal(size=(512, 4)).astype(np.float32)
    actions = (obs[:, 0] > 0).astype(np.int32)
    path = str(tmp_path / "batches.json")
    n = write_sample_batch_json(
        [{"obs": obs[:256], "actions": actions[:256]},
         {"obs": obs[256:], "actions": actions[256:]}], path)
    assert n == 2

    ds = read_sample_batch_json(path)
    assert ds.count() == 512
    got = np.concatenate([b["obs"] for b in
                          ds.iter_batches(batch_format="numpy")])
    assert got.shape == (512, 4)

    mod = MLPModule(4, 2, hidden=(32,))
    bc = BCLearner(mod, lr=1e-2)
    loss = train_offline(bc, ds, num_epochs=5, batch_size=128)
    logits, _ = mod.apply_np(bc.get_weights(), obs)
    acc = float((np.argmax(logits, -1) == actions).mean())
    assert acc > 0.9, (acc, loss)


def test_offline_parquet_sample_batches_roundtrip(rl_ray, tmp_path):
    """Offline parquet format: transitions persist as columnar rows
    (fixed-size list obs) and read back into a Dataset that drives an
    offline learner to the same accuracy as the JSON path."""
    from ray_tpu.rllib import BCLearner, MLPModule
    from ray_tpu.rllib.offline import (read_sample_batch_parquet,
                                       train_offline,
                                       write_sample_batch_parquet)

    rng = np.random.default_rng(0)
    obs = rng.normal(size=(512, 4)).astype(np.float32)
    actions = (obs[:, 0] > 0).astype(np.int32)
    path = str(tmp_path / "pq")
    n = write_sample_batch_parquet(
        [{"obs": obs[:256], "actions": actions[:256]},
         {"obs": obs[256:], "actions": actions[256:]}], path)
    assert n == 512

    ds = read_sample_batch_parquet(path)
    assert ds.count() == 512
    got = np.concatenate([b["obs"] for b in
                          ds.iter_batches(batch_format="numpy")])
    assert got.shape == (512, 4) and got.dtype == np.float32

    # >2D (image) observations round-trip with their exact shape via
    # the sidecar manifest (round-4 review find: reshape(n, -1) lost it)
    imgs = rng.normal(size=(8, 5, 6, 2)).astype(np.float32)
    p2 = str(tmp_path / "pq_img")
    write_sample_batch_parquet([{"obs": imgs,
                                 "actions": np.zeros(8, np.int32)}], p2)
    back = np.concatenate([b["obs"] for b in read_sample_batch_parquet(
        p2).iter_batches(batch_format="numpy")])
    assert back.shape == (8, 5, 6, 2)
    np.testing.assert_allclose(back, imgs)

    mod = MLPModule(4, 2, hidden=(32,))
    bc = BCLearner(mod, lr=1e-2)
    train_offline(bc, ds, num_epochs=5, batch_size=128)
    logits, _ = mod.apply_np(bc.get_weights(), obs)
    acc = float((np.argmax(logits, -1) == actions).mean())
    assert acc > 0.9, acc


# slow: 52 s alone on the 8-core sandbox and 100 s beside five other
# workers, whatever the networks' size: 18 s go to building the learner
# and compiling its update before the first step, and the bar needs some
# 1,100 updates of 23 ms after that (see CHANGES.md, PR 25).
@pytest.mark.slow
def test_dreamerv3_cartpole_learns(rl_ray):
    """DreamerV3 (compact): the RSSM world model + imagination
    actor-critic cracks CartPole — eval return well above random
    (~20) within a bounded env-step budget. Model-based RL is far more
    sample-efficient than the model-free families above, so the budget
    is small; the bar is conservative to keep CI stable."""
    from ray_tpu.rllib import DreamerV3Config

    cfg = (DreamerV3Config()
           .environment("CartPole-v1")
           .env_runners(num_envs_per_env_runner=8)
           .debugging(seed=3))
    cfg.train_kwargs.update(steps_per_iter=64, updates_per_step=1,
                            learning_starts=256, horizon=10)
    algo = cfg.build()
    try:
        best = 0.0
        for i in range(40):
            r = algo.train()
            if r["episode_return_mean"] > 60:
                best = max(best, algo.evaluate(6))
                if best >= 150:
                    break
        assert best >= 150, f"DreamerV3 best eval {best:.1f}"
    finally:
        algo.stop()
