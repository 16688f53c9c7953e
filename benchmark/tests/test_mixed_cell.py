"""What PR 30 added for ``train-laguna-1chip``: the cell end to end at a
tiny size on a CPU worker, the FLOP functions by layer kind against hand
counts, and the new readers on a reduction that has the window and held
calls and on one that lacks them (a program of another model)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import mixed_flops, scopes, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "train-laguna-1chip"
NEW = ("flash_window_roofline", "flash_attn_roofline.mixed",
       "moe_held_gmm_roofline", "mixed_mfu", "moe_held_row_share",
       "attn_proj_roofline.mixed", "mlp_roofline.mixed",
       "head_loss_roofline.mixed")


def test_cell_runs_tiny_on_cpu():
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_mixed.py")],
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"train_tok_per_s_per_chip", "setup_s"}
    assert "compilations inside the window: 0" in p.stdout
    assert "differing choices, share: 0.000e+00" in p.stdout
    assert "of 1024 routed rows a step the held experts multiplied" \
        in p.stdout


def _model():
    return spec.model_sizes(spec._read_json(os.path.join(
        spec.ROOT, "benchmark/configs/laguna-s-2.1-c1.json")))


def test_flops_against_hand_counts():
    m = _model()
    assert [(l["heads"], l["sliding"], l["routed"])
            for l in mixed_flops.layers(m)] == [
        (48, False, False), (72, True, True), (72, True, True),
        (72, True, True), (48, False, True)]
    full = 2 * 3072 * 6144 + 2 * 3072 * 1024 + 3072 * 48      # 44.19 M
    sliding = 2 * 3072 * 9216 + 2 * 3072 * 1024 + 3072 * 72   # 63.14 M
    assert mixed_flops.attn_proj_params(m, 48) == full
    assert mixed_flops.attn_proj_params(m, 72) == sliding
    routed = 3072 * 256 + 3 * 3072 * 1024            # router, shared expert
    assert mixed_flops.token_matmul_params(m) == (
        2 * full + 3 * sliding + 3 * 3072 * 12288 + 4 * routed
        + 3072 * 12544)
    assert mixed_flops.expert_params(m) == 3 * 3072 * 1024       # 9.44 M
    # the band: 512 keys a query but for the first 511 queries
    pairs = 512 * 8192 - 512 * 511 / 2
    assert mixed_flops.attention_flops_fwd(2, 8192, 72, 128, 512) == \
        2 * 72 * 4 * 128 * pairs
    assert mixed_flops.attention_flops_fwd(1, 8192, 48, 128) == \
        48 * 4 * 128 * (8192 * 8193 / 2)
    assert mixed_flops.flash_flops_per_step(m, 2, 8192, sliding=True) == \
        3.5 * 3 * 2 * 72 * 4 * 128 * pairs
    rows = 4 * 16384 * 10 / 16
    step = mixed_flops.train_flops_per_step(m, 2, 8192, rows)
    # the issue's count: 621 M multiply-adds a token, forward
    assert abs(step / 6 / 16384 / 621e6 - 1) < 0.005


@pytest.fixture
def traced_obs(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "trace_dir_of", lambda obs: str(tmp_path))

    def make(kernel_s, model, scope_self_s=None, moe_scope_self_s=None,
             **train):
        with open(tmp_path / "scopes.json", "w") as f:
            json.dump({"chips": 1, "busy_s": 1.0, "kernel_s": kernel_s,
                       "scope_self_s": scope_self_s or {}}, f)
        with open(tmp_path / "moe_scopes.json", "w") as f:
            json.dump({"chips": 1, "busy_s": 1.0,
                       "scope_self_s": moe_scope_self_s or {}}, f)
        return {"trace": {"busy_s": 1.0}, "cell": {"name": CELL},
                "model": model, "traffic": {"batch": 2, "seq": 8192},
                "device": {"device_kind": "TPU v5 lite"},
                "train": {"traced_steps": 2, "tokens_per_step": 16384,
                          "chips": 1, "untraced_steps": 10,
                          "untraced_s": 8.0, **train}}

    return make


def test_readers_on_a_reduction_with_the_calls(traced_obs):
    m = _model()
    obs = traced_obs(
        {"flash_win_fwd": 0.04, "flash_win_bwd_dq": 0.03,
         "flash_win_bwd_dkv": 0.05, "flash_fwd": 0.1, "flash_bwd_dq": 0.1,
         "flash_bwd_dkv": 0.12, "gmm": 0.02, "jvp_jit_gmm__": 0.03,
         "jvp_jit_tgmm__": 0.01, "other_gmm_like": 9.0}, m,
        # lib/scopes.py sends the routed layers' time to ``mlp``,
        # lib/moe_scopes.py keeps the dense MLP and the shared expert there
        scope_self_s={"attn_qkv": 0.38, "attn_out": 0.18, "mlp": 0.41,
                      "head_loss": 0.052},
        moe_scope_self_s={"mlp": 0.23, "moe_experts": 0.06,
                          "head_loss": 0.052},
        moe_rows_routed=655360, moe_rows_held=40000.0,
        moe_rows_held_traced=41000.0)
    got = {n: spec.metric_reader(n)(obs) for n in NEW}
    band = mixed_flops.flash_flops_per_step(m, 2, 8192, True) / 197e12
    assert got["flash_window_roofline"] == pytest.approx(100 * band / 0.06)
    full = mixed_flops.flash_flops_per_step(m, 2, 8192, False) / 197e12
    assert got["flash_attn_roofline.mixed"] == pytest.approx(
        100 * full / 0.16)
    assert got["moe_held_gmm_roofline"] == pytest.approx(
        100 * 6 * 41000 * 3 * 3072 * 1024 / 197e12 / 0.03)
    assert got["moe_held_row_share"] == pytest.approx(100 * 40000 / 655360)
    assert got["mixed_mfu"] == pytest.approx(
        100 * mixed_flops.train_flops_per_step(m, 2, 8192, 40000.0)
        * 10 / 8.0 / 197e12)
    per_token = 6 * 16384 / 197e12
    full = 2 * 3072 * 6144 + 2 * 3072 * 1024 + 3072 * 48
    sliding = 2 * 3072 * 9216 + 2 * 3072 * 1024 + 3072 * 72
    assert got["attn_proj_roofline.mixed"] == pytest.approx(
        100 * per_token * (2 * full + 3 * sliding) / 0.28)
    assert got["mlp_roofline.mixed"] == pytest.approx(
        100 * per_token * 3 * 3072 * (12288 + 4 * 1024) / 0.115)
    assert got["head_loss_roofline.mixed"] == pytest.approx(
        100 * per_token * 3072 * 12544 / 0.026)
    assert all(0 < v <= 100 for v in got.values())


def test_readers_find_nothing_in_another_models_run(traced_obs):
    """A program without the window calls and the counter (the parent's,
    or another cell's): every reader returns nothing and raises nothing."""
    olmoe = spec.model_sizes(spec._read_json(os.path.join(
        spec.ROOT, "benchmark/configs/olmoe-1b-7b-c1.json")))
    obs = traced_obs({"flash_fwd": 0.1, "gmm": 0.1}, olmoe,
                     scope_self_s={"attn_qkv": 0.1, "attn_out": 0.1,
                                   "mlp": 0.3, "head_loss": 0.1},
                     moe_scope_self_s={"mlp": 0.01, "head_loss": 0.1})
    assert [spec.metric_reader(n)(obs) for n in NEW] == [None] * len(NEW)
    assert [spec.metric_reader(n)({}) for n in NEW] == [None] * len(NEW)
