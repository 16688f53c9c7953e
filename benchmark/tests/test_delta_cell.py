"""What PR 39 added for ``train-olmo-hybrid-1chip``: the cell end to end at
a tiny size on a CPU worker, the FLOP and byte functions by layer kind
against hand counts, and the new readers on a reduction that has the
rule's scopes and on one that lacks them (a program of another model, or
the parent's)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import delta_flops, scopes, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "train-olmo-hybrid-1chip"
NEW = ("delta_mfu", "delta_rule_roofline", "gdn_proj_roofline",
       "gdn_conv_roofline", "flash_attn_roofline.delta",
       "attn_proj_roofline.delta", "mlp_roofline.delta",
       "head_loss_roofline.delta", "unscoped_device_share.delta")
# readers the benchmark had, which the cell is appended to
OLD = ("train_tok_per_s_per_chip", "host_ms_per_step")
KINDS = {"linear", "full", "top"}


def test_cell_runs_tiny_on_cpu():
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_delta.py")],
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"train_tok_per_s_per_chip", "setup_s"}
    assert "compilations inside the window: 0" in p.stdout
    for what in ("first-step loss", "per-token loss, rms",
                 "per-token loss, max", "scan state, largest |S|",
                 "scan state, a head's whole",
                 "gradient, linear", "gradient, full", "gradient, top",
                 "first step, moment, linear", "first step, moment, full",
                 "first step, moment, top", "first step, parameters"):
        assert f"[bench] {what}: " in p.stdout, what
    assert "ok=False" not in p.stdout


def test_the_parent_fails_at_once_without_the_model(monkeypatch, tmp_path):
    """A checkout from before ``ray_tpu/models/olmo_hybrid.py``: ``run``
    raises before it starts a runtime or a worker."""
    from benchmark.cells import train_delta

    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    with pytest.raises(RuntimeError,
                       match="no ray_tpu/models/olmo_hybrid.py"):
        train_delta.run({"model_config": {"module": "olmo_hybrid"}})


def _model():
    return spec.model_sizes(spec._read_json(os.path.join(
        spec.ROOT, "benchmark/configs/olmo-hybrid-7b-c1.json")))


def test_config_states_every_published_width():
    m = _model()
    assert (m["hidden_size"], m["intermediate_size"]) == (3840, 11008)
    assert (m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"]) == (30, 30, 128)
    assert (m["linear_num_key_heads"], m["linear_num_value_heads"],
            m["linear_key_head_dim"], m["linear_value_head_dim"],
            m["linear_conv_kernel_dim"]) == (30, 30, 96, 192, 4)
    assert m["linear_allow_neg_eigval"] is True
    assert m["rope_parameters"] == {"rope_theta": None}
    assert m["vocab_size"] == 12544 and m["tie_word_embeddings"] is False
    assert m["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert m["reduced_from"] == {"num_hidden_layers": 32,
                                 "vocab_size": 100352}
    assert m["vocab_size"] * 8 == m["reduced_from"]["vocab_size"]
    assert len(m["layer_types"]) == 32
    assert [m["layer_types"][l].split("_")[0] for l in m["held"]["layers"]] \
        == m["held"]["layer_kinds"]
    assert m["held"]["layers"] == list(range(m["num_hidden_layers"]))
    assert "928.7 M parameters" in m["deployment"]
    assert "7.43 GB" in m["deployment"]
    mc = m["model_config"]
    assert (mc["module"], mc["preset"]) == ("olmo_hybrid", "olmo_hybrid_7b")
    assert mc["attention_layers"] == [k == "full"
                                      for k in m["held"]["layer_kinds"]]
    # the catalog's row, where the guide is installed: every key of its
    # config as published but the two reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["name"] == "Olmo-Hybrid-7B"]
        assert m["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if m.get(k) != v} == {
            "num_hidden_layers", "vocab_size"}


def test_traffic_is_one_sequence_of_32768():
    tr = spec._read_json(os.path.join(
        spec.BENCH_DIR, "traffic", CELL + ".json"))
    assert (tr["family"], tr["batch"], tr["seq"]) == ("train_delta", 1,
                                                      32768)
    assert (tr["lr"], tr["lr_warmup_steps"]) == (0.0001, 2000)
    chk = tr["check"]
    for key in ("loss_tolerance", "token_nll_rms_tolerance",
                "token_nll_max_tolerance", "state_abs_max_tolerance",
                "state_head_gap_tolerance"):
        # (the largest gap of 32,768 positions is bounded at 0.84)
        assert 0 < chk[key] < 1, key
    for key in ("gradient_gap_tolerance", "first_step_moment_tolerance"):
        assert set(chk[key]) == KINDS
        # a step that hands on what it was given reads 1 on the moment
        assert all(0 < v < 0.5 for v in chk[key].values())
    # the rate at step 0 is 0, so the parameters may not move at all
    assert chk["first_step_param_tolerance"] == 0


def test_flops_and_bytes_against_hand_counts():
    m = _model()
    assert delta_flops.count(m, "linear") == 3
    assert delta_flops.count(m, "full") == 1
    proj = 3840 * 17340 + 5760 * 3840                       # 88.70 M
    attn = 4 * 3840 * 3840                                  # 58.98 M
    assert delta_flops.conv_dim(m) == 11520
    assert delta_flops.gdn_proj_params(m) == proj
    assert delta_flops.attn_proj_params(m) == attn
    assert delta_flops.mlp_params(m) == 4 * 3 * 3840 * 11008
    assert delta_flops.head_params(m) == 3840 * 12544
    assert delta_flops.token_matmul_params(m) == (
        3 * proj + attn + 4 * 3 * 3840 * 11008 + 3840 * 12544)
    # the issue's 880.5 M; every parameter but the embedding's rows, the
    # norms, the taps and the rule's scalars
    assert round(delta_flops.token_matmul_params(m) / 1e6, 1) == 880.5
    assert 928_862_196 - delta_flops.token_matmul_params(m) == (
        3840 * 12544 + 3 * (11520 * 4 + 2 * 30 + 192 + 2 * 3840)
        + 4 * 3840 + 3840)
    T = 32768
    assert delta_flops.attention_flops_fwd(m, 1, T) == \
        30 * 4 * 128 * (T * (T + 1) / 2)
    pairs = 64 * 65 / 2
    fwd = 512 * 30 * (pairs * (6 * 96 + 4 * 192) + 64 ** 3 / 3
                      + 6 * 64 * 96 * 192)
    assert delta_flops.rule_flops_fwd(m, 1, T) == fwd
    assert delta_flops.rule_flops_per_step(m, 1, T) == 3 * 3 * fwd
    ins, out = (11520 + 60) * 2, 5760 * 2
    assert delta_flops.rule_bytes_per_step(m, T) == \
        3 * T * (ins + out + 2 * ins + out)
    assert delta_flops.conv_bytes_per_step(m, T) == 3 * 5 * 11520 * 2 * T
    # the rule's floor a step: 7.0 ms of FLOPs, 11.1 ms of bytes
    assert delta_flops.rule_flops_per_step(m, 1, T) / 197e12 == \
        pytest.approx(7.0e-3, rel=0.01)
    assert delta_flops.rule_bytes_per_step(m, T) / 819e9 == \
        pytest.approx(11.1e-3, rel=0.01)
    # the issue's count: about 200 T a step, 6.1 G a token (199.2 and
    # 6.08: the issue counts the rule at 0.07 G a token, this file 0.04)
    step = delta_flops.train_flops_per_step(m, 1, T)
    assert abs(step / 200e12 - 1) < 0.01
    share = lambda f: round(100 * f / step, 1)
    assert share(6 * delta_flops.mlp_params(m) * T) == 50.1
    assert share(6 * 3 * proj * T) == 26.3
    assert share(6 * delta_flops.head_params(m) * T) == 4.8
    assert share(3 * delta_flops.attention_flops_fwd(m, 1, T)) == 12.4
    assert share(delta_flops.rule_flops_per_step(m, 1, T)) == 0.7


def test_scope_of_knows_the_rules_names():
    path = ("jit(step)/jvp(gdn)/gdn_rule/while/body/checkpoint/dot_general",
            "jit(step)/transpose(jvp(gdn))/gdn_conv/mul",
            "jit(step)/jvp(gdn)/gdn_in/dot_general",
            "jit(step)/transpose(jvp(gdn))/gdn_norm/rsqrt",
            "jit(step)/jvp(gdn)/reshape", "jit(step)/jvp(mlp)/dot_general",
            "jit(step)/add")
    assert [delta_flops.scope_of(p) for p in path] == [
        "gdn_rule", "gdn_conv", "gdn_in", "gdn_norm", "gdn", "mlp",
        "unscoped"]
    # the readers the benchmark had send the rule's time to unscoped
    assert scopes.scope_of(path[0]) == "unscoped"


@pytest.fixture
def traced_obs(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "trace_dir_of", lambda obs: str(tmp_path))

    def make(kernel_s, model, scope_self_s=None, **train):
        with open(tmp_path / "scopes.json", "w") as f:
            json.dump({"chips": 1, "busy_s": 4.0, "kernel_s": kernel_s,
                       "scope_self_s": {}}, f)
        with open(tmp_path / "delta_scopes.json", "w") as f:
            json.dump({"chips": 1, "busy_s": 4.0,
                       "scope_self_s": scope_self_s or {}}, f)
        return {"trace": {"busy_s": 4.0, "window_s": 4.02},
                "cell": {"name": CELL}, "model": model,
                "traffic": {"batch": 1, "seq": 32768},
                "device": {"device_kind": "TPU v5 lite"},
                "train": {"traced_steps": 2, "tokens_per_step": 32768,
                          "chips": 1, "untraced_steps": 10, "steps": 12,
                          "window_s": 34.0, "untraced_s": 28.0, **train}}

    return make


def test_readers_on_a_reduction_with_the_scopes(traced_obs):
    m = _model()
    obs = traced_obs(
        {"flash_fwd": 0.26, "flash_bwd_dq": 0.15, "flash_bwd_dkv": 0.20}, m,
        scope_self_s={
            "gdn_in": 0.80, "gdn_out": 0.32, "gdn_conv": 0.08,
            "gdn_rule": 1.20, "gdn_norm": 0.20, "gdn": 0.01,
            "attn_qkv": 0.16, "attn_out": 0.06, "flash": 0.62, "mlp": 1.70,
            "head_loss": 0.16, "embed": 0.01, "unscoped": 0.20})
    got = {n: spec.metric_reader(n)(obs) for n in NEW + OLD}
    per_token = 6 * 32768 / 197e12
    assert got["gdn_proj_roofline"] == pytest.approx(
        100 * per_token * 3 * (3840 * 17340 + 5760 * 3840) / 0.56)
    floor = delta_flops.rule_bytes_per_step(m, 32768) / 819e9
    assert floor > delta_flops.rule_flops_per_step(m, 1, 32768) / 197e12
    assert got["delta_rule_roofline"] == pytest.approx(100 * floor / 0.60)
    assert got["gdn_conv_roofline"] == pytest.approx(
        100 * (3 * 5 * 11520 * 2 * 32768 / 819e9) / 0.04)
    assert got["flash_attn_roofline.delta"] == pytest.approx(
        100 * delta_flops.flash_flops_per_step(m, 1, 32768) / 197e12 / 0.305)
    assert got["mlp_roofline.delta"] == pytest.approx(
        100 * per_token * 4 * 3 * 3840 * 11008 / 0.85)
    assert got["head_loss_roofline.delta"] == pytest.approx(
        100 * per_token * 3840 * 12544 / 0.08)
    assert got["attn_proj_roofline.delta"] == pytest.approx(
        100 * per_token * 4 * 3840 * 3840 / 0.11)
    assert got["unscoped_device_share.delta"] == pytest.approx(5.0)
    assert got["delta_mfu"] == pytest.approx(
        100 * delta_flops.train_flops_per_step(m, 1, 32768) * 10 / 28.0
        / 197e12)
    # the readers the benchmark had, on this cell's observations
    assert got["train_tok_per_s_per_chip"] == pytest.approx(
        12 * 32768 / 34.0)
    assert got["host_ms_per_step"] == pytest.approx(10.0)
    assert all(0 < got[n] <= 100 for n in NEW)


def test_readers_find_nothing_in_another_models_run(traced_obs):
    """A program without the rule's scopes (the parent's, or another
    cell's), and a model without delta-rule layers: every new reader
    returns nothing and raises nothing."""
    granite = spec.model_sizes(spec._read_json(os.path.join(
        spec.ROOT, "benchmark/configs/granite-4.0-h-micro-c1.json")))
    obs = traced_obs({"flash_fwd": 0.1}, granite,
                     scope_self_s={"attn_qkv": 0.1, "attn_out": 0.1,
                                   "mlp": 0.3, "head_loss": 0.1})
    assert [spec.metric_reader(n)(obs) for n in NEW] == [None] * len(NEW)
    # this model's cell, run on a program that names none of the scopes
    bare = traced_obs({"flash_fwd": 0.1}, _model(),
                      scope_self_s={"unscoped": 1.0})
    got = {n: spec.metric_reader(n)(bare) for n in NEW}
    assert {n for n, v in got.items() if v is not None} <= {
        "flash_attn_roofline.delta", "delta_mfu"}
    assert [spec.metric_reader(n)({}) for n in NEW] == [None] * len(NEW)


def test_benchmark_json_appends_one_configuration_and_one_cell():
    b = spec.load_benchmark()
    assert [c["name"] for c in b["configs"]][-1] == "olmo-hybrid-7b-c1"
    assert [w["name"] for w in b["workloads"]][-1] == CELL
    assert len(b["configs"]) == len(b["workloads"]) == 7
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
        "setup_programs_compiled"}
