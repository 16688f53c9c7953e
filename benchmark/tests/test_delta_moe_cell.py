"""What PR 50 added for ``train-qwen3-next-1chip``: the cell end to end at
a tiny size on a CPU worker, the configuration against the catalog's row,
the FLOP and byte functions against hand counts, and the new readers on a
reduction that has the scopes and on one that lacks them (a program of
another model, or the parent's)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import delta_moe_flops as lib
from benchmark.lib import scopes, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "train-qwen3-next-1chip"
NEW = ("delta_moe_mfu", "delta_rule_roofline.grouped",
       "gdn_proj_roofline.grouped", "gdn_conv_roofline.grouped",
       "flash_attn_roofline.h256", "attn_proj_roofline.gated",
       "moe_shared_roofline", "head_loss_roofline.delta_moe",
       "unscoped_device_share.delta_moe")
# readers the benchmark had, which the cell is appended to
OLD = ("train_tok_per_s_per_chip", "host_ms_per_step",
       "expert_load_max_over_mean", "moe_held_gmm_roofline",
       "moe_held_row_share")
KINDS = {"linear", "full", "top"}


def test_cell_runs_tiny_on_cpu():
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_delta_moe.py")],
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"train_tok_per_s_per_chip", "setup_s"}
    assert "compilations inside the window: 0" in p.stdout
    for what in ("first-step loss", "per-token loss, rms",
                 "per-token loss, max", "scan state, largest |S|",
                 "scan state, a head's whole", "router logits, rms",
                 "router logits, max", "differing choices, share",
                 "differing choices, regret",
                 "gradient, linear", "gradient, full", "gradient, top",
                 "first step, moment, linear", "first step, moment, full",
                 "first step, moment, top", "first step, parameters"):
        assert f"[bench] {what}: " in p.stdout, what
    assert "ok=False" not in p.stdout


def test_the_limits_refuse_every_control_at_a_tiny_size():
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "delta_moe_limits.py"),
         "--tiny"], capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    verdicts = {k: v for k, v in out.items()
                if isinstance(v, dict) and "correct" in v}
    assert len(verdicts) == 9 and verdicts.pop("program")["correct"]
    assert all(not v["correct"] and v["refused_by"]
               for v in verdicts.values())
    assert set(verdicts["step_that_hands_on_what_it_was_given"][
        "refused_by"]) == {f"first step, moment, {k}" for k in KINDS}


def test_the_parent_fails_at_once_without_the_model(monkeypatch, tmp_path):
    """A checkout from before ``ray_tpu/models/qwen3_next.py``: ``run``
    raises before it starts a runtime or a worker."""
    from benchmark.cells import train_delta_moe

    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    with pytest.raises(RuntimeError,
                       match="no ray_tpu/models/qwen3_next.py"):
        train_delta_moe.run({"model_config": {"module": "qwen3_next"}})


def _model():
    return spec.model_sizes(spec._read_json(os.path.join(
        spec.ROOT, "benchmark/configs/qwen3-next-80b-a3b-c1.json")))


def test_config_states_every_published_width():
    m = _model()
    assert (m["hidden_size"], m["intermediate_size"]) == (2048, 5120)
    assert (m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"]) == (16, 2, 256)
    assert (m["linear_num_key_heads"], m["linear_num_value_heads"],
            m["linear_key_head_dim"], m["linear_value_head_dim"],
            m["linear_conv_kernel_dim"]) == (16, 32, 128, 128, 4)
    assert (m["moe_intermediate_size"], m["shared_expert_intermediate_size"],
            m["num_experts_per_tok"], m["norm_topk_prob"]) == (
        512, 512, 10, True)
    assert (m["partial_rotary_factor"], m["rope_theta"]) == (0.25, 10000000)
    assert m["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert m["reduced_from"] == {"num_hidden_layers": 48, "num_experts": 512,
                                 "vocab_size": 151936}
    assert m["vocab_size"] * 8 == m["reduced_from"]["vocab_size"]
    assert m["num_experts"] * 8 == m["held"]["num_experts_routed_over"] == 512
    assert m["held"]["layer_kinds"] == ["linear", "linear", "linear", "full"]
    assert "1,028,320,320 parameters" in m["deployment"]
    assert "eight chips share each layer" in m["deployment"]
    assert {"router_aux_loss_coef", "multi_token_prediction", "rule_chunk",
            "in_projection_columns", "sequence"} <= set(m["assumed"])
    mc = m["model_config"]
    assert (mc["module"], mc["preset"]) == ("qwen3_next",
                                            "qwen3_next_80b_a3b")
    assert mc["experts_held"] == [0, m["num_experts"]]
    assert mc["num_experts"] == 512 and mc["top_k"] == 10
    assert mc["attention_layers"] == [k == "full"
                                      for k in m["held"]["layer_kinds"]]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["name"] == "Qwen3-Next-80B-A3B-Instruct"]
        assert m["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if m.get(k) != v} == {
            "num_hidden_layers", "num_experts", "vocab_size"}


def test_the_program_holds_what_the_file_counts():
    """1,028,320,320 parameters, from the program's own shapes."""
    jax = pytest.importorskip("jax")
    import numpy as np

    from benchmark.cells.train_hybrid import load_model

    model, _, cfg = load_model(_model()["model_config"])
    shapes = jax.eval_shape(lambda k: model.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(shapes)) == 1_028_320_320


def test_traffic_is_one_sequence_of_32768():
    tr = spec._read_json(os.path.join(
        spec.BENCH_DIR, "traffic", CELL + ".json"))
    assert (tr["family"], tr["batch"], tr["seq"]) == ("train_delta_moe", 1,
                                                      32768)
    assert (tr["lr"], tr["lr_warmup_steps"]) == (0.0001, 2000)
    chk = tr["check"]
    for key in ("loss_tolerance", "token_nll_rms_tolerance",
                "state_abs_max_tolerance", "state_head_gap_tolerance",
                "router_logit_rms_tolerance",
                "differing_choice_share_tolerance"):
        assert 0 < chk[key] < 1, key
    for key in ("gradient_gap_tolerance", "first_step_moment_tolerance"):
        assert set(chk[key]) == KINDS
        # a step that hands on what it was given reads 1 on the moment
        assert all(0 < v < 0.5 for v in chk[key].values())
    # the rate at step 0 is 0, so the parameters may not move at all
    assert chk["first_step_param_tolerance"] == 0


def test_flops_and_bytes_against_hand_counts():
    m = _model()
    assert (lib.count(m, "linear"), lib.count(m, "full")) == (3, 1)
    proj = 2048 * 12352 + 4096 * 2048
    attn = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    assert lib.conv_dim(m) == 8192
    assert lib.gdn_proj_params(m) == proj
    assert lib.attn_proj_params(m) == attn
    assert lib.shared_params(m) == 3 * 2048 * 512 + 2048
    assert lib.router_params(m) == 2048 * 512
    assert lib.expert_params(m) == 3_145_728
    assert lib.head_params(m) == 2048 * 18992
    # every parameter but the embedding's rows, the experts, the norms, the
    # taps and the rule's scalars
    assert 1_028_320_320 - lib.token_matmul_params(m) == (
        2048 * 18992 + 4 * 64 * 3_145_728
        + 3 * (8192 * 4 + 2 * 32 + 128 + 2048) + (2 * 256 + 2048)
        + 4 * 2048 + 2048)
    T = 32768
    assert lib.attention_flops_fwd(m, 1, T) == 16 * 4 * 256 * (
        T * (T + 1) / 2)
    pairs = 64 * 65 / 2
    fwd = 512 * 32 * (pairs * (6 * 128 + 4 * 128) + 64 ** 3 / 3
                      + 6 * 64 * 128 * 128)
    assert lib.rule_flops_per_step(m, 1, T) == 3 * 3 * fwd
    # q and k once at the 16 key heads, v, a and b at the 32 value heads
    ins, out = (8192 + 64) * 2, 4096 * 2
    assert lib.rule_bytes_per_step(m, T) == 3 * T * (ins + out + 2 * ins
                                                     + out)
    assert lib.conv_bytes_per_step(m, T) == 3 * 5 * 8192 * 2 * T
    # the rule's floor a step: 6.8 ms of FLOPs over 6.9 ms of bytes... the
    # larger bounds it
    assert lib.rule_flops_per_step(m, 1, T) / 197e12 == pytest.approx(
        6.77e-3, rel=0.01)
    step = lib.train_flops_per_step(m, 1, T, 4 * 40960)
    assert step / 1e12 == pytest.approx(66.99, rel=1e-3)
    share = lambda f: round(100 * f / step, 1)
    assert share(3 * lib.attention_flops_fwd(m, 1, T)) == 39.4
    assert share(6 * 3 * proj * T) == 29.7
    assert share(6 * lib.head_params(m) * T) == 11.4
    assert share(6 * attn * T) == 8.0
    assert share(lib.experts_train_flops(m, 4 * 40960)) == 4.6
    assert share(lib.rule_flops_per_step(m, 1, T)) == 2.0


def test_scope_of_knows_the_new_names():
    path = ("jit(step)/jvp(gdn)/gdn_rule/delta_rule_fwd",
            "jit(step)/jvp(mlp)/moe_shared/dot_general",
            "jit(step)/jvp(mlp)/moe_shared/moe_shared_gate/logistic",
            "jit(step)/jvp(attn_out)/attn_gate/mul",
            "jit(step)/jvp(mlp)/moe_route/top_k",
            "jit(step)/jvp(gdn_pre_norm)/rsqrt",
            "jit(step)/jvp(mlp)/rsqrt", "jit(step)/add")
    assert [lib.scope_of(p) for p in path] == [
        "gdn_rule", "moe_shared", "moe_shared_gate", "attn_gate",
        "moe_route", "gdn_pre_norm", "mlp", "unscoped"]


@pytest.fixture
def traced_obs(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "trace_dir_of", lambda obs: str(tmp_path))

    def make(kernel_s, model, scope_self_s=None, **train):
        with open(tmp_path / "scopes.json", "w") as f:
            json.dump({"chips": 1, "busy_s": 4.0, "kernel_s": kernel_s,
                       "scope_self_s": {}}, f)
        for name in ("delta_moe_scopes.json", "moe_scopes.json"):
            with open(tmp_path / name, "w") as f:
                json.dump({"chips": 1, "busy_s": 4.0,
                           "scope_self_s": scope_self_s or {}}, f)
        return {"trace": {"busy_s": 4.0, "window_s": 4.02},
                "cell": {"name": CELL}, "model": model,
                "traffic": {"batch": 1, "seq": 32768},
                "device": {"device_kind": "TPU v5 lite"},
                "train": {"traced_steps": 4, "tokens_per_step": 32768,
                          "chips": 1, "untraced_steps": 50, "steps": 54,
                          "window_s": 50.0, "untraced_s": 45.0, **train}}

    return make


_SCOPES = {"gdn_in": 0.40, "gdn_out": 0.12, "gdn_pre_norm": 0.02,
           "gdn_conv": 0.06, "gdn_rule": 0.80, "gdn_norm": 0.10,
           "gdn": 0.01, "attn_qkv": 0.12, "attn_out": 0.05,
           "attn_gate": 0.01, "flash": 0.02, "mlp": 0.05,
           "moe_shared": 0.14, "moe_shared_gate": 0.02, "moe_route": 0.20,
           "moe_dispatch": 0.10, "moe_experts": 0.15, "moe_combine": 0.05,
           "head_loss": 0.20, "embed": 0.01, "unscoped": 0.20}


def test_readers_on_a_reduction_with_the_scopes(traced_obs):
    m = _model()
    obs = traced_obs(
        {"flash_fwd": 0.40, "flash_bwd_dq": 0.30, "flash_bwd_dkv": 0.40,
         "gmm": 0.05, "jvp_jit_tgmm__": 0.05}, m, scope_self_s=_SCOPES,
        moe_rows_held=163840.0, moe_rows_held_traced=163840.0,
        moe_rows_routed=4 * 327680, expert_load_max_over_mean=1.1)
    got = {n: spec.metric_reader(n)(obs) for n in NEW + OLD}
    per_token = 6 * 32768 / 197e12
    assert got["gdn_proj_roofline.grouped"] == pytest.approx(
        100 * per_token * 3 * (2048 * 12352 + 4096 * 2048) / (0.54 / 4))
    floor = max(lib.rule_flops_per_step(m, 1, 32768) / 197e12,
                lib.rule_bytes_per_step(m, 32768) / 819e9)
    assert got["delta_rule_roofline.grouped"] == pytest.approx(
        100 * floor / 0.20)
    assert got["gdn_conv_roofline.grouped"] == pytest.approx(
        100 * (3 * 5 * 8192 * 2 * 32768 / 819e9) / 0.015)
    assert got["flash_attn_roofline.h256"] == pytest.approx(
        100 * lib.flash_flops_per_step(m, 1, 32768) / 197e12 / 0.275)
    assert got["attn_proj_roofline.gated"] == pytest.approx(
        100 * per_token * lib.attn_proj_params(m) / 0.045)
    assert got["moe_shared_roofline"] == pytest.approx(
        100 * per_token * 4 * lib.shared_params(m) / 0.04)
    assert got["head_loss_roofline.delta_moe"] == pytest.approx(
        100 * per_token * 2048 * 18992 / 0.05)
    assert got["unscoped_device_share.delta_moe"] == pytest.approx(5.0)
    assert got["delta_moe_mfu"] == pytest.approx(
        100 * lib.train_flops_per_step(m, 1, 32768, 163840) * 50 / 45.0
        / 197e12)
    # the readers the benchmark had, on this cell's observations
    assert got["train_tok_per_s_per_chip"] == pytest.approx(
        54 * 32768 / 50.0)
    assert got["moe_held_row_share"] == pytest.approx(12.5)
    assert got["moe_held_gmm_roofline"] == pytest.approx(
        100 * 6 * 3_145_728 * 163840 / 197e12 / (0.10 / 4))
    assert got["expert_load_max_over_mean"] == 1.1
    assert all(0 < got[n] <= 100 for n in NEW)


def test_readers_find_nothing_in_another_models_run(traced_obs):
    """A program without the new scopes (the parent's, or another cell's),
    and another model: every new reader returns nothing and raises
    nothing."""
    olmo = spec.model_sizes(spec._read_json(os.path.join(
        spec.ROOT, "benchmark/configs/olmo-hybrid-7b-c1.json")))
    obs = traced_obs({"flash_fwd": 0.1}, olmo, scope_self_s=_SCOPES)
    assert [spec.metric_reader(n)(obs) for n in NEW] == [None] * len(NEW)
    bare = traced_obs({"flash_fwd": 0.1}, _model(),
                      scope_self_s={"unscoped": 1.0})
    got = {n: spec.metric_reader(n)(bare) for n in NEW}
    assert {n for n, v in got.items() if v is not None} <= {
        "flash_attn_roofline.h256"}
    assert [spec.metric_reader(n)({}) for n in NEW] == [None] * len(NEW)


def test_benchmark_json_appends_one_configuration_and_one_cell():
    b = spec.load_benchmark()
    assert [c["name"] for c in b["configs"]][-1] == "qwen3-next-80b-a3b-c1"
    assert [w["name"] for w in b["workloads"]][-1] == CELL
    assert len(b["configs"]) == len(b["workloads"]) == 10
    cell = b["workloads"][-1]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert len(b["configs"][-1]["why"]) <= 200
    mine = [m for m in b["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == list(NEW)
    assert [m["name"] for m in b["per_layer"]][-len(NEW):] == list(NEW)
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
        "moe_held_gmm_roofline", "moe_held_row_share"}
