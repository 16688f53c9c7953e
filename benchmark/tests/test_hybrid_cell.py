"""What PR 32 added for ``train-lfm2-1chip``: the cell end to end at a
tiny size on a CPU worker, the FLOP and byte functions by layer kind
against hand counts, and the new readers on a reduction that has the
convolution's scopes and on one that lacks them (a program of another
model, or the parent's)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import hybrid_flops, scopes, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "train-lfm2-1chip"
NEW = ("hybrid_mfu", "short_conv_roofline", "short_conv_mix_roofline",
       "flash_attn_roofline.hybrid", "mlp_roofline.hybrid",
       "attn_proj_roofline.hybrid", "head_loss_roofline.hybrid",
       "moe_route_share", "unscoped_device_share.hybrid")
# readers the benchmark had, which the cell is appended to
OLD = ("moe_dispatch_share", "expert_load_max_over_mean",
       "moe_held_gmm_roofline", "moe_held_row_share", "host_ms_per_step")


def test_cell_runs_tiny_on_cpu():
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_hybrid.py")],
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"train_tok_per_s_per_chip", "setup_s"}
    assert "compilations inside the window: 0" in p.stdout
    assert "differing choices, share: 0.000e+00" in p.stdout
    assert "router bias after the first step: 0.000e+00" in p.stdout
    assert "choices under the routers' biases, regret" in p.stdout
    assert "of 512 routed rows a step the held experts multiplied" \
        in p.stdout


def test_the_parent_fails_at_once_without_the_model(monkeypatch, tmp_path):
    """A checkout from before ``ray_tpu/models/lfm2.py``: ``run`` raises
    before it starts a runtime or a worker."""
    from benchmark.cells import train_hybrid

    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    with pytest.raises(RuntimeError, match="no ray_tpu/models/lfm2.py"):
        train_hybrid.run({"model_config": {"module": "lfm2"}})


def _model():
    return spec.model_sizes(spec._read_json(os.path.join(
        spec.ROOT, "benchmark/configs/lfm2-8b-a1b-c1.json")))


def test_config_states_every_published_width():
    m = _model()
    assert (m["hidden_size"], m["intermediate_size"],
            m["moe_intermediate_size"]) == (2048, 7168, 1792)
    assert (m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"], m["conv_L_cache"]) == (32, 8, 64, 3)
    assert (m["held"]["num_experts_routed_over"],
            m["num_experts_per_tok"]) == (32, 4)
    assert m["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert m["reduced_from"] == {"num_hidden_layers": 24, "num_experts": 32,
                                 "vocab_size": 65536}
    kinds = ["conv+dense" if t == "conv" and l < m["num_dense_layers"]
             else {"conv": "conv", "full_attention": "attn"}[t]
             for l, t in enumerate(m["layer_types"])]
    assert [kinds[l] for l in m["held"]["layers"]] == \
        m["held"]["layer_kinds"]
    mc = m["model_config"]
    assert mc["attention_layers"] == [k == "attn"
                                      for k in m["held"]["layer_kinds"]]
    assert mc["experts_held"] == [0, m["num_experts"]]
    assert mc["num_experts"] == m["held"]["num_experts_routed_over"]


def test_flops_and_bytes_against_hand_counts():
    m = _model()
    assert hybrid_flops.layers(m) == [
        {"attn": False, "routed": False}, {"attn": True, "routed": True},
        {"attn": False, "routed": True}, {"attn": False, "routed": True},
        {"attn": False, "routed": True}]
    conv = 2048 * 6144 + 2048 * 2048                       # 16.78 M
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512                # 10.49 M
    assert hybrid_flops.conv_proj_params(m) == conv
    assert hybrid_flops.attn_proj_params(m) == attn
    assert hybrid_flops.mlp_params(m) == 3 * 2048 * 7168   # 44.04 M
    assert hybrid_flops.expert_params(m) == 3 * 2048 * 1792     # 11.01 M
    assert hybrid_flops.token_matmul_params(m) == (
        4 * conv + attn + 3 * 2048 * 7168 + 4 * 2048 * 32 + 2048 * 32768)
    assert hybrid_flops.attention_flops_fwd(m, 2, 8192) == \
        2 * 32 * 4 * 64 * (8192 * 8193 / 2)
    assert hybrid_flops.flash_flops_per_step(m, 2, 8192) == \
        3.5 * hybrid_flops.attention_flops_fwd(m, 2, 8192)
    # the issue's count: 28.9 T a step at balance (131,072 held rows)
    step = hybrid_flops.train_flops_per_step(m, 2, 8192, 131072)
    assert abs(step / 28.9e12 - 1) < 0.01
    # the pass: 4 + 7 widths a token and layer, 4 more under remat
    assert hybrid_flops.conv_mix_bytes_per_step(m, 16384, remat=False) == \
        4 * 11 * 2048 * 2 * 16384
    assert hybrid_flops.conv_mix_bytes_per_step(m, 16384, remat=True) == \
        4 * 15 * 2048 * 2 * 16384


def test_scope_of_knows_the_convolutions_names():
    path = ("jit(step)/transpose(jvp(mlp))/moe_route/moe_bias_update/sign",
            "jit(step)/jvp(short_conv)/conv_mix/mul",
            "jit(step)/transpose(jvp(short_conv))/conv_in/dot_general",
            "jit(step)/jvp(mlp)/moe_route/top_k", "jit(step)/add")
    assert [hybrid_flops.scope_of(p) for p in path] == [
        "moe_bias_update", "conv_mix", "conv_in", "moe_route", "unscoped"]
    # the readers the benchmark had send a convolution's time to unscoped
    assert scopes.scope_of(path[1]) == "unscoped"


@pytest.fixture
def traced_obs(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "trace_dir_of", lambda obs: str(tmp_path))

    def make(kernel_s, model, hybrid_scope_self_s=None, **train):
        with open(tmp_path / "scopes.json", "w") as f:
            json.dump({"chips": 1, "busy_s": 1.0, "kernel_s": kernel_s,
                       "scope_self_s": {}}, f)
        with open(tmp_path / "moe_scopes.json", "w") as f:
            json.dump({"chips": 1, "busy_s": 1.0, "scope_self_s":
                       {k: v for k, v in (hybrid_scope_self_s or {}).items()
                        if k.startswith("moe_")}}, f)
        with open(tmp_path / "hybrid_scopes.json", "w") as f:
            json.dump({"chips": 1, "busy_s": 1.0,
                       "scope_self_s": hybrid_scope_self_s or {}}, f)
        return {"trace": {"busy_s": 1.0, "window_s": 1.02},
                "cell": {"name": CELL}, "model": model,
                "traffic": {"batch": 2, "seq": 8192},
                "device": {"device_kind": "TPU v5 lite"},
                "train": {"traced_steps": 2, "tokens_per_step": 16384,
                          "chips": 1, "untraced_steps": 10,
                          "untraced_s": 4.0, **train}}

    return make


def test_readers_on_a_reduction_with_the_scopes(traced_obs):
    m = _model()
    obs = traced_obs(
        {"flash_fwd": 0.04, "flash_bwd_dq": 0.02, "flash_bwd_dkv": 0.03,
         "gmm": 0.05, "jvp_jit_gmm__": 0.06, "jvp_jit_tgmm__": 0.03}, m,
        hybrid_scope_self_s={
            "conv_in": 0.07, "conv_mix": 0.024, "conv_out": 0.034,
            "attn_qkv": 0.02, "attn_out": 0.007, "flash": 0.09, "mlp": 0.07,
            "head_loss": 0.08, "moe_route": 0.022, "moe_bias_update": 0.002,
            "moe_dispatch": 0.09, "moe_combine": 0.08, "moe_experts": 0.17,
            "embed": 0.006, "unscoped": 0.1},
        moe_rows_routed=262144, moe_rows_held=131000.0,
        moe_rows_held_traced=131100.0, expert_load_max_over_mean=1.05)
    got = {n: spec.metric_reader(n)(obs) for n in NEW + OLD}
    per_token = 6 * 16384 / 197e12
    assert got["short_conv_roofline"] == pytest.approx(
        100 * per_token * 4 * (2048 * 6144 + 2048 * 2048) / 0.064)
    assert got["short_conv_mix_roofline"] == pytest.approx(
        100 * (4 * 15 * 2048 * 2 * 16384 / 819e9) / 0.012)
    assert got["flash_attn_roofline.hybrid"] == pytest.approx(
        100 * hybrid_flops.flash_flops_per_step(m, 2, 8192) / 197e12 / 0.045)
    assert got["attn_proj_roofline.hybrid"] == pytest.approx(
        100 * per_token * (2 * 2048 * 2048 + 2 * 2048 * 512) / 0.0135)
    assert got["mlp_roofline.hybrid"] == pytest.approx(
        100 * per_token * 3 * 2048 * 7168 / 0.035)
    assert got["head_loss_roofline.hybrid"] == pytest.approx(
        100 * per_token * 2048 * 32768 / 0.04)
    assert got["moe_route_share"] == pytest.approx(2.4)
    assert got["unscoped_device_share.hybrid"] == pytest.approx(10.0)
    assert got["hybrid_mfu"] == pytest.approx(
        100 * hybrid_flops.train_flops_per_step(m, 2, 8192, 131000.0)
        * 10 / 4.0 / 197e12)
    # the readers the benchmark had, on this cell's observations
    assert got["moe_dispatch_share"] == pytest.approx(19.2)
    assert got["moe_held_gmm_roofline"] == pytest.approx(
        100 * 6 * 131100 * 3 * 2048 * 1792 / 197e12 / 0.07)
    assert got["moe_held_row_share"] == pytest.approx(
        100 * 131000 / 262144)
    assert got["expert_load_max_over_mean"] == 1.05
    assert got["host_ms_per_step"] == pytest.approx(10.0)
    assert all(0 < got[n] <= 100 for n in NEW)


def test_readers_find_nothing_in_another_models_run(traced_obs):
    """A program without the convolution's scopes (the parent's, or
    another cell's), and a model without convolution layers: every new
    reader returns nothing and raises nothing."""
    laguna = spec.model_sizes(spec._read_json(os.path.join(
        spec.ROOT, "benchmark/configs/laguna-s-2.1-c1.json")))
    obs = traced_obs({"flash_fwd": 0.1, "gmm": 0.1}, laguna,
                     hybrid_scope_self_s={"attn_qkv": 0.1, "attn_out": 0.1,
                                          "mlp": 0.3, "head_loss": 0.1,
                                          "moe_route": 0.05},
                     moe_rows_routed=655360, moe_rows_held=40000.0)
    assert [spec.metric_reader(n)(obs) for n in NEW] == [None] * len(NEW)
    # this model's cell, run on a program that names none of the scopes
    bare = traced_obs({"flash_fwd": 0.1}, _model(),
                      hybrid_scope_self_s={"unscoped": 1.0})
    got = {n: spec.metric_reader(n)(bare) for n in NEW}
    assert {n for n, v in got.items() if v is not None} <= {
        "flash_attn_roofline.hybrid"}
    assert [spec.metric_reader(n)({}) for n in NEW] == [None] * len(NEW)
