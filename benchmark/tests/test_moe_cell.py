"""What PR 26 added for ``train-olmoe-1chip``: the cell end to end at a
tiny size on a CPU worker, the FLOP functions against hand counts, and
the new readers on a recorded trace that lacks the routed layer's scopes
(the parent commit's program) and on a reduction that has them."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import moe_flops, moe_scopes, scopes, spec

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(os.path.dirname(HERE), "fixtures",
                       "train_scoped.xplane.pb")
CELL = "train-olmoe-1chip"


def test_cell_runs_tiny_on_cpu():
    p = subprocess.run([sys.executable, os.path.join(HERE, "rehearse_moe.py")],
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"train_tok_per_s_per_chip", "setup_s"}
    assert line["device"]["platform"] == "cpu"
    assert "compilations inside the window: 0" in p.stdout
    assert "differing choices, share: 0.000e+00" in p.stdout


def _model(layers):
    cfg = spec._read_json(os.path.join(
        spec.ROOT, "benchmark/configs/olmoe-1b-7b-c1.json"))
    return dict(spec.model_sizes(cfg), num_hidden_layers=layers)


def test_flops_against_hand_counts():
    full = _model(16)
    # OLMoE-1B-7B: 6.92 B parameters; 1.28 B of them are a token's
    # (its matmuls' and the embedding row's table)
    assert abs(moe_flops.total_params(full) / 6.92e9 - 1) < 0.001
    active = moe_flops.active_matmul_params(full) + 50304 * 2048
    assert abs(active / 1.28e9 - 1) < 0.005
    per_layer = (4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024)
    assert moe_flops.active_matmul_params(full) == \
        16 * per_layer + 2048 * 50304
    cut = _model(3)
    assert moe_flops.total_params(cut) == 1_464_756_224  # init_params' count
    assert moe_flops.routed_params_per_token(cut) == 3 * 8 * 3 * 2048 * 1024
    per_tok = moe_flops.train_flops_per_token(cut, 4096)
    assert abs(per_tok / 1.979e9 - 1) < 0.001
    assert moe_flops.experts_train_flops(cut, 8192) == \
        6.0 * 3 * 8 * 3 * 2048 * 1024 * 8192


def test_scope_of_knows_the_routed_layers_scopes():
    path = ("jit(step)/transpose(jvp(mlp))/while/body/closed_call/"
            "checkpoint/mlp/moe_experts/ragged_dot:")
    assert moe_scopes.scope_of(path) == "moe_experts"
    assert scopes.scope_of(path) == "mlp"       # the dense cells' reader
    assert moe_scopes.scope_of("jit(step)/jvp(mlp)/mul") == "mlp"
    assert moe_scopes.scope_of("jit(step)/transpose(jvp(moe_combine))/"
                               "moe_combine/gather") == "moe_combine"
    assert moe_scopes.scope_of("jit(step)/adamw/mul") == "unscoped"
    assert moe_scopes.scope_of("jit(step)/my_moe_routes/x") == "unscoped"


def test_reduction_of_a_trace_without_the_scopes():
    """The dense program's recorded trace: the same scope times as
    ``lib/scopes.py`` reads, adding up to the busy time."""
    got = moe_scopes.reduce_scopes(FIXTURE)
    want = scopes.reduce_scopes(FIXTURE)
    assert got["chips"] == want["chips"] >= 1
    assert got["scope_self_s"] == pytest.approx(want["scope_self_s"])
    assert sum(got["scope_self_s"].values()) == pytest.approx(got["busy_s"])
    assert not set(got["scope_self_s"]) & set(moe_scopes.MOE_SCOPES)


@pytest.fixture
def traced_obs(tmp_path, monkeypatch):
    """An obs whose trace directory holds the two cached reductions."""
    monkeypatch.setattr(scopes, "trace_dir_of", lambda obs: str(tmp_path))

    def make(scope_self_s, kernel_s, model):
        busy = sum(scope_self_s.values())
        with open(tmp_path / "moe_scopes.json", "w") as f:
            json.dump({"chips": 1, "busy_s": busy,
                       "scope_self_s": scope_self_s}, f)
        with open(tmp_path / "scopes.json", "w") as f:
            json.dump({"chips": 1, "busy_s": busy, "kernel_s": kernel_s,
                       "scope_self_s": {}}, f)
        return {"trace": {"busy_s": busy}, "cell": {"name": CELL},
                "model": model, "traffic": {"batch": 2, "seq": 4096},
                "device": {"device_kind": "TPU v5 lite"},
                "train": {"traced_steps": 2, "tokens_per_step": 8192,
                          "chips": 1, "untraced_steps": 10,
                          "untraced_s": 2.0,
                          "expert_load_max_over_mean": 1.25}}

    return make


def _readers():
    names = ("moe_mfu", "moe_experts_roofline", "moe_dispatch_share",
             "expert_load_max_over_mean", "moe_gmm_roofline",
             "flash_attn_roofline.moe")
    return {n: spec.metric_reader(n) for n in names}


def test_readers_on_a_reduction_with_the_scopes(traced_obs):
    m = _model(3)
    need_s = 6.0 * 3 * 8 * 3 * 2048 * 1024 * 8192 / 197e12     # 0.0377 s
    obs = traced_obs(
        {"moe_experts": 2 * 0.1, "moe_route": 0.01, "moe_dispatch": 0.02,
         "moe_combine": 0.03, "mlp": 0.001, "flash": 0.04, "unscoped": 0.139},
        {"gmm": 2 * 0.06, "tgmm": 2 * 0.02, "flash_fwd": 0.01,
         "flash_bwd_dq": 0.01, "flash_bwd_dkv": 0.02}, m)
    got = {n: r(obs) for n, r in _readers().items()}
    assert got["moe_experts_roofline"] == pytest.approx(100 * need_s / 0.1)
    assert got["moe_gmm_roofline"] == pytest.approx(100 * need_s / 0.08)
    assert got["moe_dispatch_share"] == pytest.approx(100 * 0.06 / 0.44)
    assert got["expert_load_max_over_mean"] == 1.25
    flash_s = 3.5 * 3 * 2 * 16 * 4 * 4096 * 4096 * 128 / 2 / 197e12
    assert got["flash_attn_roofline.moe"] == pytest.approx(
        100 * flash_s / 0.02)
    rate = 10 * 8192 / 2.0
    assert got["moe_mfu"] == pytest.approx(
        100 * moe_flops.train_flops_per_token(m, 4096) * rate / 197e12)
    assert all(0 < v <= 100 for n, v in got.items()
               if n != "expert_load_max_over_mean")


def test_readers_find_nothing_in_a_dense_run(traced_obs):
    """The parent's program, or a dense cell: no routed scope, no
    grouped-matmul call, no expert count; every reader returns nothing
    and raises nothing."""
    dense = {k: v for k, v in _model(3).items()
             if k not in ("num_experts", "num_experts_per_tok")}
    obs = traced_obs({"mlp": 0.2, "flash": 0.04, "unscoped": 0.02},
                     {}, dense)
    del obs["train"]["expert_load_max_over_mean"]
    assert [r(obs) for r in _readers().values()] == [None] * 6
    assert [r({}) for r in _readers().values()] == [None] * 6
