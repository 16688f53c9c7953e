"""What PR 52 added for ``train-nemotron3-super-1chip``: the cell end to end
at a tiny size on a CPU worker, the limits' controls at that size, the
configuration against the catalog's row, the FLOP and byte functions against
hand counts, and the new readers on a reduction that has the scopes and on
one that lacks them (a program of another model, or the parent's)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import scan_moe_flops as lib
from benchmark.lib import scopes, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "train-nemotron3-super-1chip"
CONFIG = "nemotron-3-super-120b-a12b-c1"
NEW = ("scan_moe_mfu", "ssd_scan_roofline.g8", "ssm_proj_roofline.g8",
       "ssm_conv_roofline.g8", "flash_attn_roofline.scan_moe",
       "attn_proj_roofline.scan_moe", "moe_latent_proj_roofline",
       "moe_shared_roofline.relu2", "moe_held_gmm_roofline.latent",
       "moe_route_share.scan_moe", "mtp_share", "mtp_roofline",
       "head_loss_roofline.scan_moe", "unscoped_device_share.scan_moe")
# readers the benchmark had, which the cell is appended to
OLD = ("train_tok_per_s_per_chip", "host_ms_per_step",
       "expert_load_max_over_mean", "moe_held_row_share",
       "moe_dispatch_share")
KINDS = {"mamba", "attention", "moe", "mtp_attention", "mtp_moe", "top"}


def test_cell_runs_tiny_on_cpu():
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_scan_moe.py")],
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"train_tok_per_s_per_chip", "setup_s"}
    assert "compilations inside the window: 0" in p.stdout
    for what in ("first-step loss", "first-step cross entropy, module",
                 "per-token loss, rms", "per-token loss, max",
                 "per-token loss of the module, rms",
                 "per-token loss of the module, max",
                 "scan state, largest |S|", "scan state, a head's whole",
                 "router logits, rms", "router logits, max",
                 "differing choices, share", "differing choices, regret",
                 "choices under the routers' biases, regret",
                 "router bias after the first step",
                 "first step, parameters",
                 *(f"gradient, {k}" for k in KINDS),
                 *(f"first step, moment, {k}" for k in KINDS)):
        assert f"[bench] {what}: " in p.stdout, what
    assert "ok=False" not in p.stdout


def test_the_limits_refuse_every_control_at_a_tiny_size():
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "scan_moe_limits.py"), "--tiny"],
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    verdicts = {k: v for k, v in out.items()
                if isinstance(v, dict) and "correct" in v}
    assert len(verdicts) == 10 and verdicts.pop("program")["correct"]
    assert all(not v["correct"] and v["refused_by"]
               for v in verdicts.values())
    assert set(verdicts["step_that_hands_on_what_it_was_given"][
        "refused_by"]) == {f"first step, moment, {k}" for k in KINDS}
    assert "first-step loss" in verdicts["step_without_the_modules_term"][
        "refused_by"]
    assert "per-token loss of the module, rms" in verdicts[
        "program_with_the_module_reading_the_next_token"]["refused_by"]


def test_the_parent_fails_at_once_without_the_model(monkeypatch, tmp_path):
    """A checkout from before ``ray_tpu/models/nemotron_h.py``: ``run``
    raises before it starts a runtime or a worker."""
    from benchmark.cells import train_scan_moe

    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    with pytest.raises(RuntimeError,
                       match="no ray_tpu/models/nemotron_h.py"):
        train_scan_moe.run({"model_config": {"module": "nemotron_h"}})


def _model():
    return spec.model_sizes(spec._read_json(os.path.join(
        spec.ROOT, "benchmark/configs", CONFIG + ".json")))


def test_config_states_every_published_width():
    m = _model()
    assert (m["hidden_size"], m["intermediate_size"]) == (4096, 2688)
    assert (m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"]) == (32, 2, 128)
    assert (m["mamba_num_heads"], m["mamba_head_dim"], m["ssm_state_size"],
            m["n_groups"], m["conv_kernel"], m["chunk_size"]) == (
        128, 64, 128, 8, 4, 128)
    assert (m["moe_latent_size"], m["moe_intermediate_size"],
            m["moe_shared_expert_intermediate_size"],
            m["num_experts_per_tok"], m["routed_scaling_factor"],
            m["norm_topk_prob"], m["mlp_hidden_act"]) == (
        1024, 2688, 5376, 22, 5, True, "relu2")
    assert (m["num_nextn_predict_layers"],
            m["mtp_hybrid_override_pattern"]) == (1, "*E")
    assert m["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"]
    assert m["reduced_from"] == {"num_hidden_layers": 88,
                                 "n_routed_experts": 512,
                                 "vocab_size": 131072}
    assert m["vocab_size"] * 8 == m["reduced_from"]["vocab_size"]
    assert m["held"]["num_experts_routed_over"] == 512
    assert m["held"]["layers"] == list(range(26, 37))
    kinds = {"M": "mamba", "E": "moe", "*": "attention"}
    assert m["held"]["layer_kinds"] == [
        kinds[c] for c in m["hybrid_override_pattern"][26:37]]
    assert m["held"]["mtp_layer_kinds"] == ["attention", "moe"]
    assert "1,378,724,736 parameters" in m["deployment"]
    assert "64 chips share each layer" in m["deployment"]
    assert {"train_dtypes", "rope", "mtp_loss_scaling_factor", "mtp_join",
            "mtp_depth", "router_bias", "sequence", "held_headroom",
            "rescale_prenorm_residual", "time_step_limit"} <= set(
        m["assumed"])
    mc = m["model_config"]
    assert (mc["module"], mc["preset"]) == (
        "nemotron_h", "nemotron_3_super_120b_a12b")
    assert mc["experts_held"] == [0, m["n_routed_experts"]]
    assert mc["num_experts"] == 512 and mc["top_k"] == 22
    assert mc["layer_pattern"] == m["hybrid_override_pattern"][26:37]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            (row,) = [r for r in map(json.loads, f) if r["name"]
                      == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"]
        assert m["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if m.get(k) != v} == {
            "num_hidden_layers", "n_routed_experts", "vocab_size"}


def test_the_program_holds_what_the_file_counts():
    """1,378,724,736 parameters, from the program's own shapes."""
    jax = pytest.importorskip("jax")
    import numpy as np

    from benchmark.cells.train_hybrid import load_model

    model, _, cfg = load_model(_model()["model_config"])
    shapes = jax.eval_shape(lambda k: model.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(shapes)) == 1_378_724_736


def test_traffic_is_one_sequence_of_8192_and_two_ids_ahead():
    tr = spec._read_json(os.path.join(
        spec.BENCH_DIR, "traffic", CELL + ".json"))
    assert (tr["family"], tr["batch"], tr["seq"], tr["ids_ahead"]) == (
        "train_scan_moe", 1, 8192, 2)
    assert (tr["lr"], tr["lr_warmup_steps"], tr["host_batches"],
            tr["warmup_steps"], tr["trace_from_step"], tr["trace_steps"]
            ) == (0.0001, 2000, 64, 2, 3, 4)
    chk = tr["check"]
    for key in ("loss_tolerance", "mtp_loss_tolerance",
                "token_nll_rms_tolerance", "mtp_nll_rms_tolerance",
                "state_abs_max_tolerance", "state_head_gap_tolerance",
                "router_logit_rms_tolerance",
                "differing_choice_share_tolerance"):
        assert 0 < chk[key] < 1, key
    for key in ("gradient_gap_tolerance", "first_step_moment_tolerance"):
        assert set(chk[key]) == KINDS
        # a step that hands on what it was given reads 1 on the moment
        assert all(0 < v < 0.5 for v in chk[key].values())
    # the rate at step 0 is 0, so the parameters may not move at all; the
    # bias's move is a sign of integer differences
    assert chk["first_step_param_tolerance"] == 0
    assert chk["router_bias_tolerance"] == 0


def test_flops_and_bytes_against_hand_counts():
    m = _model()
    assert (lib.count(m, "mamba"), lib.count(m, "moe"),
            lib.count(m, "attention")) == (5, 6, 2)
    assert (lib.count(m, "moe", False), lib.count(m, "moe", True)) == (5, 1)
    proj = 4096 * 18560 + 8192 * 4096
    attn = 2 * 4096 * 4096 + 2 * 4096 * 256
    mix = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
    assert lib.ssm_conv_dim(m) == 10240
    assert lib.ssm_proj_params(m) == proj
    assert lib.attn_proj_params(m) == attn
    assert lib.mixture_params(m) == mix
    assert lib.expert_params(m) == 5_505_024
    assert lib.head_params(m) == 4096 * 16384
    assert lib.module_matmul_params(m) == (
        8192 * 4096 + attn + mix + 4096 * 16384)
    # every parameter but the embedding's rows, the experts, the norms, the
    # taps, the scans' scalars and the routers' biases; the head counted
    # twice (two passes)
    assert 1_378_724_736 - (lib.token_matmul_params(m)
                            - lib.head_params(m)) == (
        4096 * 16384 + 6 * 8 * 5_505_024
        + 5 * (10240 * 5 + 3 * 128 + 8192 + 4096) + 2 * 4096
        + 6 * (4096 + 512) + 4096 + 3 * 4096)
    T = 8192
    assert lib.attention_flops_fwd(m, 1, T) == 2 * 32 * 4 * 128 * (
        T * (T + 1) / 2)
    assert lib.attention_flops_fwd(m, 1, T, module=True) * 2 == (
        lib.attention_flops_fwd(m, 1, T))
    pairs = 128 * 129 / 2
    fwd = 64 * (2 * pairs * (8 * 128 + 128 * 64) + 4 * 128 * 128 * 64 * 128)
    assert lib.scan_flops_per_step(m, 1, T) == 3 * 5 * fwd
    ins, out = (8192 + 2048) * 2 + 128 * 4, 8192 * 2
    assert lib.scan_bytes_per_step(m, T) == 5 * T * (ins + out + 2 * ins
                                                     + out)
    assert lib.conv_bytes_per_step(m, T) == 5 * 5 * 10240 * 2 * T
    held = 6 * 2816.0
    step = lib.train_flops_per_step(m, 1, T, held)
    assert step / 1e12 == pytest.approx(59.28, rel=1e-3)
    share = lambda f: round(100 * f / step, 1)   # noqa: E731
    assert share(6 * 5 * proj * T) == 45.4
    assert share(lib.scan_flops_per_step(m, 1, T)) == 1.1
    assert share(6 * 6 * mix * T) == 27.1
    assert share(lib.experts_train_flops(m, held)) == 0.9
    assert share(lib.module_flops_per_step(m, 1, T, held / 6)) == 18.8
    assert share(6 * 2 * lib.head_params(m) * T) == 11.1
    assert share(3 * lib.attention_flops_fwd(m, 1, T)) == 5.6
    # the scan's floor a step: 3.4 ms of FLOPs under 4.8 ms of bytes
    assert lib.scan_bytes_per_step(m, T) / 819e9 == pytest.approx(
        4.79e-3, rel=0.01)


def test_scope_of_knows_the_new_names():
    path = ("jit(step)/jvp(ssm)/ssm_scan/ssd_scan_fwd",
            "jit(step)/jvp(mlp)/moe_latent/dot_general",
            "jit(step)/jvp(mlp)/moe_shared/dot_general",
            "jit(step)/jvp(mtp)/mtp_join/dot_general",
            "jit(step)/transpose(jvp(mtp))/mlp/moe_route/top_k",
            "jit(step)/jvp(mtp)/mtp_head/head_loss/dot_general",
            "jit(step)/jvp(mtp)/flash/flash_fwd",
            "jit(step)/moe_route/moe_bias_update/sign",
            "jit(step)/jvp(mlp)/rsqrt", "jit(step)/add")
    assert [lib.scope_of(p) for p in path] == [
        "ssm_scan", "moe_latent", "moe_shared", "mtp/mtp_join",
        "mtp/moe_route", "mtp/head_loss", "mtp/flash", "moe_bias_update",
        "mlp", "unscoped"]


@pytest.fixture
def traced_obs(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "trace_dir_of", lambda obs: str(tmp_path))

    def make(kernel_s, model, scope_self_s=None, **train):
        with open(tmp_path / "scopes.json", "w") as f:
            json.dump({"chips": 1, "busy_s": 2.0, "kernel_s": kernel_s,
                       "scope_self_s": {}}, f)
        for name in ("scan_moe_scopes.json", "moe_scopes.json"):
            with open(tmp_path / name, "w") as f:
                json.dump({"chips": 1, "busy_s": 2.0,
                           "scope_self_s": scope_self_s or {}}, f)
        return {"trace": {"busy_s": 2.0, "window_s": 2.02},
                "cell": {"name": CELL}, "model": model,
                "traffic": {"batch": 1, "seq": 8192},
                "device": {"device_kind": "TPU v5 lite"},
                "train": {"traced_steps": 4, "tokens_per_step": 8192,
                          "chips": 1, "untraced_steps": 80, "steps": 84,
                          "window_s": 50.0, "untraced_s": 47.6, **train}}

    return make


_SCOPES = {"ssm_in": 0.30, "ssm_out": 0.14, "ssm_conv": 0.06,
           "ssm_scan": 0.30, "ssm_norm": 0.08, "ssm": 0.01,
           "attn_qkv": 0.03, "attn_out": 0.02, "flash": 0.01, "mlp": 0.04,
           "moe_latent": 0.06, "moe_shared": 0.20, "moe_route": 0.10,
           "moe_bias_update": 0.004, "moe_dispatch": 0.04,
           "moe_experts": 0.04, "moe_combine": 0.02, "head_loss": 0.07,
           "embed": 0.006, "unscoped": 0.10,
           "mtp/mtp_join": 0.04, "mtp/attn_qkv": 0.03, "mtp/attn_out": 0.02,
           "mtp/flash": 0.01, "mtp/mlp": 0.01, "mtp/moe_latent": 0.012,
           "mtp/moe_shared": 0.04, "mtp/moe_route": 0.02,
           "mtp/moe_dispatch": 0.008, "mtp/moe_experts": 0.008,
           "mtp/moe_combine": 0.004, "mtp/head_loss": 0.07}


def test_readers_on_a_reduction_with_the_scopes(traced_obs):
    m = _model()
    obs = traced_obs(
        {"flash_fwd": 0.08, "flash_bwd_dq": 0.06, "flash_bwd_dkv": 0.08,
         "gmm": 0.02, "jvp_jit_tgmm__": 0.012}, m, scope_self_s=_SCOPES,
        moe_rows_held=16896.0, moe_rows_held_traced=16896.0,
        moe_rows_held_module_traced=2816.0, moe_rows_routed=6 * 180224,
        expert_load_max_over_mean=4.1)
    got = {n: spec.metric_reader(n)(obs) for n in NEW + OLD}
    per_token = 6 * 8192 / 197e12
    assert got["ssm_proj_roofline.g8"] == pytest.approx(
        100 * per_token * 5 * lib.ssm_proj_params(m) / (0.44 / 4))
    floor = max(lib.scan_flops_per_step(m, 1, 8192) / 197e12,
                lib.scan_bytes_per_step(m, 8192) / 819e9)
    assert got["ssd_scan_roofline.g8"] == pytest.approx(100 * floor / 0.075)
    assert got["ssm_conv_roofline.g8"] == pytest.approx(
        100 * (5 * 5 * 10240 * 2 * 8192 / 819e9) / 0.015)
    assert got["flash_attn_roofline.scan_moe"] == pytest.approx(
        100 * lib.flash_flops_per_step(m, 1, 8192) / 197e12 / 0.055)
    assert got["attn_proj_roofline.scan_moe"] == pytest.approx(
        100 * per_token * 2 * lib.attn_proj_params(m) / 0.025)
    assert got["moe_latent_proj_roofline"] == pytest.approx(
        100 * per_token * 6 * lib.latent_params(m) / 0.018)
    assert got["moe_shared_roofline.relu2"] == pytest.approx(
        100 * per_token * 6 * lib.shared_params(m) / 0.06)
    assert got["moe_held_gmm_roofline.latent"] == pytest.approx(
        100 * 6 * 5_505_024 * 16896 / 197e12 / (0.032 / 4))
    assert got["moe_route_share.scan_moe"] == pytest.approx(
        100 * 0.124 / 2.0)
    under = sum(v for k, v in _SCOPES.items() if k.startswith("mtp/"))
    assert got["mtp_share"] == pytest.approx(100 * under / 2.0)
    assert got["mtp_roofline"] == pytest.approx(
        100 * lib.module_flops_per_step(m, 1, 8192, 2816.0) / 197e12
        / (under / 4))
    assert got["head_loss_roofline.scan_moe"] == pytest.approx(
        100 * per_token * 2 * 4096 * 16384 / 0.035)
    assert got["unscoped_device_share.scan_moe"] == pytest.approx(5.0)
    assert got["scan_moe_mfu"] == pytest.approx(
        100 * lib.train_flops_per_step(m, 1, 8192, 16896) * 80 / 47.6
        / 197e12)
    # the readers the benchmark had, on this cell's observations
    assert got["train_tok_per_s_per_chip"] == pytest.approx(84 * 8192 / 50.0)
    assert got["moe_held_row_share"] == pytest.approx(100 / 64)
    assert got["expert_load_max_over_mean"] == 4.1
    assert all(got[n] > 0 for n in NEW)     # (the times are made up)


def test_readers_find_nothing_in_another_models_run(traced_obs):
    """A program without the new scopes (the parent's, or another cell's),
    and another model: every new reader returns nothing and raises
    nothing."""
    granite = spec.model_sizes(spec._read_json(os.path.join(
        spec.ROOT, "benchmark/configs/granite-4.0-h-micro-c1.json")))
    obs = traced_obs({"flash_fwd": 0.1}, granite, scope_self_s=_SCOPES)
    assert [spec.metric_reader(n)(obs) for n in NEW] == [None] * len(NEW)
    bare = traced_obs({"flash_fwd": 0.1}, _model(),
                      scope_self_s={"unscoped": 1.0})
    got = {n: spec.metric_reader(n)(bare) for n in NEW}
    assert {n for n, v in got.items() if v is not None} <= {
        "flash_attn_roofline.scan_moe"}
    assert [spec.metric_reader(n)({}) for n in NEW] == [None] * len(NEW)


def test_benchmark_json_appends_one_configuration_and_one_cell():
    b = spec.load_benchmark()
    assert [c["name"] for c in b["configs"]].count(CONFIG) == 1
    (cell,) = [w for w in b["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, CELL, 1)
    assert len(cell["why"]) <= 200
    assert all(len(c["why"]) <= 200 for c in b["configs"])
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    mine = [m for m in b["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == list(NEW)
    assert all(m["moves"] == "train_tok_per_s_per_chip" and m["unit"] == "%"
               for m in mine)
    appended = {m["name"] for m in b["end_to_end"] + b["per_layer"]
                if CELL in m.get("workloads", ()) and m not in mine}
    assert appended == {
        "train_tok_per_s_per_chip", "host_ms_per_step",
        "device_idle_share.train", "setup_runtime_s", "setup_gang_s",
        "setup_chip_open_s", "setup_trace_lower_s", "setup_compile_s",
        "setup_programs_compiled", "loop_wait_ms_p50",
        "loop_cpu_ms_per_wait", "loop_stalled_share", "proc_paused_share",
        "moe_dispatch_share", "expert_load_max_over_mean",
        "moe_held_row_share"}
    # the existing reader of the held passes' kernels counts three matrices
    # at the hidden width: the cell is not on its list
    (old,) = [m for m in b["per_layer"]
              if m["name"] == "moe_held_gmm_roofline"]
    assert CELL not in old["workloads"]
