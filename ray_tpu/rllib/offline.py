"""Offline RL: behavior cloning and conservative Q-learning over Datasets.

Reference: rllib/algorithms/bc/bc.py and rllib/algorithms/cql/cql.py —
there, offline data flows through offline_data readers into the learner;
here the input is a ``ray_tpu.data.Dataset`` (any datasource), iterated
with ``iter_batches`` and fed to a jitted update, so the streaming
executor (backpressure, prefetch) is the offline-data pipeline.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from ray_tpu.rllib.rl_module import MLPModule, QMLPModule, to_numpy


class BCLearner:
    """Behavior cloning for discrete actions: maximize logp(a_data | s)."""

    def __init__(self, module: MLPModule, lr: float = 1e-3, seed: int = 0):
        import jax
        import optax

        self.module = module
        self.seed = seed
        self.params = module.init_params(seed)
        self.tx = optax.adam(lr)
        self.opt_state = self.tx.init(self.params)
        self._update = jax.jit(self._update_impl, donate_argnums=(0, 1))

    def _loss(self, params, obs, actions):
        import jax
        import jax.numpy as jnp

        logits, _ = self.module.apply(params, obs)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, actions[:, None], axis=-1)[:, 0]
        return nll.mean()

    def _update_impl(self, params, opt_state, obs, actions):
        import jax

        loss, grads = jax.value_and_grad(self._loss)(params, obs, actions)
        updates, opt_state = self.tx.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
        return params, opt_state, loss

    def update(self, batch: Dict[str, np.ndarray]) -> float:
        import jax.numpy as jnp

        self.params, self.opt_state, loss = self._update(
            self.params, self.opt_state,
            jnp.asarray(batch["obs"], jnp.float32),
            jnp.asarray(batch["actions"], jnp.int32))
        return float(loss)

    def get_weights(self):
        return to_numpy(self.params)


class CQLLearner:
    """Discrete CQL(H): double-DQN TD loss + conservative regularizer
    ``alpha_cql * (logsumexp_a Q(s, a) - Q(s, a_data))`` (Kumar et al.
    2020), which penalizes Q on out-of-distribution actions.
    """

    def __init__(self, module: QMLPModule, lr: float = 1e-3,
                 gamma: float = 0.99, tau: float = 0.01,
                 alpha_cql: float = 1.0, seed: int = 0):
        import jax
        import optax

        self.module = module
        self.seed = seed
        self.params = module.init_params(seed)
        import jax.numpy as jnp

        self.target_params = jax.tree_util.tree_map(jnp.array, self.params)
        self.tx = optax.adam(lr)
        self.opt_state = self.tx.init(self.params)
        self._gamma = gamma
        self._tau = tau
        self._alpha = alpha_cql
        self._update = jax.jit(self._update_impl, donate_argnums=(0, 1, 2))

    def _loss(self, params, target_params, mb):
        import jax
        import jax.numpy as jnp

        q = self.module.apply(params, mb["obs"])
        q_sa = jnp.take_along_axis(q, mb["actions"][:, None], axis=-1)[:, 0]
        a_next = jnp.argmax(self.module.apply(params, mb["next_obs"]),
                            axis=-1)
        q_next = jnp.take_along_axis(
            self.module.apply(target_params, mb["next_obs"]),
            a_next[:, None], axis=-1)[:, 0]
        target = jax.lax.stop_gradient(
            mb["rewards"] + self._gamma * (1.0 - mb["dones"]) * q_next)
        td_loss = jnp.square(q_sa - target).mean()
        conservative = (jax.nn.logsumexp(q, axis=-1) - q_sa).mean()
        return td_loss + self._alpha * conservative

    def _update_impl(self, params, target_params, opt_state, mb):
        import jax

        loss, grads = jax.value_and_grad(self._loss)(params, target_params,
                                                     mb)
        updates, opt_state = self.tx.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
        target_params = jax.tree_util.tree_map(
            lambda t, p: t + self._tau * (p - t), target_params, params)
        return params, target_params, opt_state, loss

    def update(self, batch: Dict[str, np.ndarray]) -> float:
        import jax.numpy as jnp

        mb = {
            "obs": jnp.asarray(batch["obs"], jnp.float32),
            "actions": jnp.asarray(batch["actions"], jnp.int32),
            "rewards": jnp.asarray(batch["rewards"], jnp.float32),
            "next_obs": jnp.asarray(batch["next_obs"], jnp.float32),
            "dones": jnp.asarray(batch["dones"], jnp.float32),
        }
        self.params, self.target_params, self.opt_state, loss = (
            self._update(self.params, self.target_params, self.opt_state,
                         mb))
        return float(loss)

    def get_weights(self):
        return to_numpy(self.params)


class MARWILLearner:
    """Monotonic Advantage Re-Weighted Imitation Learning (reference:
    rllib/algorithms/marwil/marwil.py — Wang et al. 2018). Cloning
    weighted by exponentiated advantage: the policy imitates the data's
    GOOD actions more than its bad ones, interpolating between pure BC
    (beta=0) and policy improvement. A value head regresses returns; the
    advantage for the weight is ``R - V(s)`` with a running-norm
    (reference: MARWIL's moving average of squared advantages)."""

    def __init__(self, module: MLPModule, lr: float = 1e-3,
                 beta: float = 1.0, vf_coef: float = 1.0, seed: int = 0):
        import jax
        import optax

        self.module = module
        self.seed = seed
        self.params = module.init_params(seed)
        self.tx = optax.adam(lr)
        self.opt_state = self.tx.init(self.params)
        self._beta = beta
        self._vf_coef = vf_coef
        self._ma_adv_sq = 1.0  # running norm (host-side, like the ref)
        self._update = jax.jit(self._update_impl, donate_argnums=(0, 1))

    def _loss(self, params, obs, actions, returns, adv_norm):
        import jax
        import jax.numpy as jnp

        logits, values = self.module.apply(params, obs)
        logp = jax.nn.log_softmax(logits)
        logp_a = jnp.take_along_axis(logp, actions[:, None], axis=-1)[:, 0]
        adv = jax.lax.stop_gradient(returns - values)
        weight = jnp.exp(self._beta * jnp.clip(adv / adv_norm, -5.0, 5.0))
        pg_loss = -(jax.lax.stop_gradient(weight) * logp_a).mean()
        vf_loss = jnp.square(values - returns).mean()
        return (pg_loss + self._vf_coef * vf_loss,
                (jnp.square(adv).mean(),))

    def _update_impl(self, params, opt_state, obs, actions, returns,
                     adv_norm):
        import jax

        (loss, aux), grads = jax.value_and_grad(self._loss, has_aux=True)(
            params, obs, actions, returns, adv_norm)
        updates, opt_state = self.tx.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(lambda p, u: p + u, params,
                                        updates)
        return params, opt_state, loss, aux[0]

    def update(self, batch: Dict[str, np.ndarray]) -> float:
        import jax.numpy as jnp

        adv_norm = max(self._ma_adv_sq, 1e-8) ** 0.5
        self.params, self.opt_state, loss, adv_sq = self._update(
            self.params, self.opt_state,
            jnp.asarray(batch["obs"], jnp.float32),
            jnp.asarray(batch["actions"], jnp.int32),
            jnp.asarray(batch["returns"], jnp.float32),
            jnp.asarray(adv_norm, jnp.float32))
        self._ma_adv_sq = (0.99 * self._ma_adv_sq
                           + 0.01 * float(adv_sq))
        return float(loss)

    def get_weights(self):
        return to_numpy(self.params)


def train_offline(learner, dataset, *, num_epochs: int = 1,
                  batch_size: int = 256, shuffle: bool = True) -> float:
    """Drive a BC/CQL learner over a Dataset; returns the last loss.

    With ``shuffle``, each epoch re-executes the pipeline with a full
    ``random_shuffle`` (new permutation per epoch, drawn from the
    learner's seed: the same seed trains the same way twice).
    """
    loss = float("nan")
    for epoch in range(num_epochs):
        ds = (dataset.random_shuffle(seed=learner.seed + epoch)
              if shuffle else dataset)
        for batch in ds.iter_batches(batch_size=batch_size):
            if len(next(iter(batch.values()))) < 2:
                continue
            loss = learner.update(batch)
    return loss


def write_sample_batch_json(batches, path: str) -> int:
    """Persist sample batches as JSON-lines (reference:
    rllib/offline/json_writer.py — one JSON object per batch, array
    columns as lists). Returns the number of batches written."""
    import json

    n = 0
    with open(path, "w") as f:
        for batch in batches:
            obj = {k: np.asarray(v).tolist() for k, v in batch.items()}
            f.write(json.dumps(obj) + "\n")
            n += 1
    return n


def read_sample_batch_json(paths):
    """Load JSON-lines sample batches into a row-per-transition Dataset
    ready for ``train_offline`` (reference: rllib/offline/json_reader.py
    feeding the learner; here the Data streaming executor IS the
    offline pipeline)."""
    import json

    from ray_tpu import data as rdata

    ds = rdata.read_text(paths)

    def expand(batch):
        cols: Dict[str, list] = {}
        for line in np.asarray(batch["text"]).ravel().tolist():
            obj = json.loads(line)
            for k, v in obj.items():
                cols.setdefault(k, []).append(np.asarray(v))
        return {k: np.concatenate(v, axis=0) for k, v in cols.items()}

    return ds.map_batches(expand, batch_format="numpy")


def write_sample_batch_parquet(batches, path: str) -> int:
    """Persist sample batches as parquet, one row per TRANSITION with
    array columns as fixed-width lists (reference:
    rllib/offline/output_writer + the parquet path of offline_data; the
    columnar format is what large offline corpora actually ship as).
    ``path`` is a directory; returns the number of rows written."""
    import json
    import os

    import pyarrow as pa
    import pyarrow.parquet as pq

    os.makedirs(path, exist_ok=True)
    total = 0
    shapes: Dict[str, list] = {}
    for i, batch in enumerate(batches):
        cols = {}
        n = len(next(iter(batch.values())))
        for k, v in batch.items():
            arr = np.asarray(v)
            shp = list(arr.shape[1:])
            if shapes.setdefault(k, shp) != shp:
                raise ValueError(
                    f"column {k!r} has inconsistent trailing shapes "
                    f"across batches: {shapes[k]} vs {shp}")
            if arr.ndim == 1:
                cols[k] = pa.array(arr)
            else:
                # [n, d...] -> flat list column; the trailing shape goes
                # to the sidecar manifest so >2D observations (images)
                # round-trip exactly like the JSON format
                flat = arr.reshape(n, -1)
                cols[k] = pa.FixedSizeListArray.from_arrays(
                    pa.array(flat.ravel()), flat.shape[1])
        table = pa.table(cols)
        pq.write_table(table, os.path.join(path, f"batch-{i:06d}.parquet"))
        total += n
    with open(os.path.join(path, "_shapes.json"), "w") as f:
        json.dump(shapes, f)
    return total


def read_sample_batch_parquet(paths):
    """Load parquet sample batches into a row-per-transition Dataset for
    ``train_offline`` — nested list columns stack back to [n, d] float
    arrays; the streaming executor is the offline pipeline (reference:
    rllib/offline/json_reader.py's role, columnar)."""
    import json
    import os

    from ray_tpu import data as rdata

    shapes: Dict[str, list] = {}
    for root in ([paths] if isinstance(paths, str) else paths):
        m = os.path.join(root, "_shapes.json")
        if os.path.isdir(root) and os.path.exists(m):
            shapes.update(json.load(open(m)))
    ds = rdata.read_parquet(paths)

    def to_arrays(batch):
        out = {}
        for k, v in batch.items():
            arr = np.asarray(v)
            if arr.dtype == object:  # list column -> stacked array
                arr = np.stack([np.asarray(x) for x in arr.ravel()])
            shp = shapes.get(k)
            if shp and list(arr.shape[1:]) != shp:
                arr = arr.reshape((arr.shape[0], *shp))
            out[k] = arr
        return out

    return ds.map_batches(to_arrays, batch_format="numpy")
