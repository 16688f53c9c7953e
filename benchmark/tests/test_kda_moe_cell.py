"""What PR 60 added for ``train-ling3-flash-1chip``: the cell end to end at
a tiny size on a CPU worker, its planted controls, the configuration
against the catalog's row, the FLOP and byte functions against hand counts,
and the new readers on a reduction that has the scopes and on one that
lacks them (a program of another model, or the parent's)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import kda_moe_flops as lib
from benchmark.lib import scopes, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "train-ling3-flash-1chip"
CONFIG = "ling-3.0-flash-vl-c1"
NEW = ("kda_moe_mfu", "kda_rule_roofline", "kda_proj_roofline",
       "kda_conv_roofline", "kda_gate_share", "mla_flash_roofline.kda_moe",
       "mla_proj_roofline.kda_moe", "moe_shared_roofline.kda_moe",
       "head_loss_roofline.kda_moe", "unscoped_device_share.kda_moe",
       "moe_route_share.kda_moe")
# readers the benchmark had, which the cell is appended to
OLD = ("train_tok_per_s_per_chip", "host_ms_per_step", "moe_dispatch_share",
       "expert_load_max_over_mean", "moe_held_gmm_roofline",
       "moe_held_row_share")
KINDS = {"kda+dense", "kda+moe", "mla+moe", "top"}


def test_cell_runs_tiny_on_cpu():
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_kda_moe.py")],
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
                 "choices in the program's own scores, regret",
                 "weights in the program's own scores, gap",
                 "router bias after the first step",
                 "choices under the routers' biases, regret",
                 "weights under the routers' biases, gap",
                 "smallest log decay of a step, over the bound",
                 "first step, parameters",
                 *(f"gradient, {k}" for k in KINDS),
                 *(f"gradient, median leaf, {k}" for k in KINDS),
                 *(f"first step, moment, {k}" for k in KINDS)):
        assert f"[bench] {what}: " in p.stdout, what
    assert "ok=False" not in p.stdout


def test_the_limits_refuse_every_control_at_a_tiny_size():
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "kda_moe_limits.py"), "--tiny"],
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    verdicts = {k: v for k, v in out.items()
                if isinstance(v, dict) and "correct" in v}
    assert len(verdicts) == 13
    assert verdicts.pop("program")["correct"]
    assert verdicts.pop("program_with_biases")["correct"]
    assert all(not v["correct"] and v["refused_by"]
               for v in verdicts.values())
    assert "program_with_a_heads_decay_the_mean_of_its_channels" in verdicts
    assert set(verdicts["step_that_hands_on_what_it_was_given"][
        "refused_by"]) == {f"first step, moment, {k}" for k in KINDS}
    assert "choices in the program's own scores, regret" in verdicts[
        "program_without_the_group_limit"]["refused_by"]
    assert "weights in the program's own scores, gap" in verdicts[
        "program_with_the_bias_in_the_weights"]["refused_by"]


def test_the_parent_fails_at_once_without_the_model(monkeypatch, tmp_path):
    """A checkout from before ``ray_tpu/models/ling3.py``: ``run`` raises
    before it starts a runtime or a worker."""
    from benchmark.cells import train_kda_moe

    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    with pytest.raises(RuntimeError, match="no ray_tpu/models/ling3.py"):
        train_kda_moe.run({"model_config": {"module": "ling3"}})


def _model():
    return spec.model_sizes(spec._read_json(os.path.join(
        spec.ROOT, "benchmark/configs", CONFIG + ".json")))


def test_config_states_every_published_width():
    m = _model()
    assert (m["hidden_size"], m["intermediate_size"]) == (2560, 6144)
    assert (m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"]) == (32, 32, 128)
    assert (m["q_lora_rank"], m["kv_lora_rank"], m["qk_nope_head_dim"],
            m["qk_rope_head_dim"], m["v_head_dim"]) == (None, 512, 128, 64,
                                                        128)
    assert (m["moe_intermediate_size"],
            m["moe_shared_expert_intermediate_size"],
            m["num_experts_per_tok"], m["n_group"], m["topk_group"],
            m["routed_scaling_factor"]) == (768, 768, 8, 8, 4, 2.5)
    assert (m["layer_group_size"], m["first_k_dense_replace"],
            m["short_conv_kernel_size"], m["kda_lower_bound"]) == (6, 2, 4,
                                                                   -5)
    assert m["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert m["reduced_from"] == {"num_hidden_layers": 42, "num_experts": 512,
                                 "vocab_size": 157184}
    assert m["vocab_size"] * 8 == m["reduced_from"]["vocab_size"]
    assert m["held"]["num_experts_routed_over"] == 512
    assert m["held"]["layers"] == [0, 2, 3, 4, 5, 6, 7]
    assert m["held"]["layer_kinds"] == [
        "kda+dense", "kda+moe", "kda+moe", "kda+moe", "mla+moe", "kda+moe",
        "kda+moe"]
    assert {"kda_output_gate", "kda_gate", "kda_taps", "use_qk_norm",
            "bias_update_rate", "router_aux_loss", "swiglu_clamps",
            "not_built", "sequence"} <= set(m["assumed"])
    assert "822,036,416 parameters" in m["deployment"]
    assert "retreat" in m
    mc = m["model_config"]
    assert (mc["module"], mc["preset"]) == ("ling3", "ling_3_flash")
    assert mc["experts_held"] == [0, m["num_experts"]]
    assert mc["num_experts"] == 512 and mc["top_k"] == 8
    assert mc["layer_ids"] == m["held"]["layers"]
    assert mc["q_lora_rank"] is None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["name"] == "Ling-3.0-flash-VL"]
        assert m["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if m.get(k) != v} == {
            "num_hidden_layers", "num_experts", "vocab_size"}


def test_the_program_holds_what_the_file_counts():
    """822,036,416 parameters, from the program's own shapes."""
    jax = pytest.importorskip("jax")
    import numpy as np

    from benchmark.cells.train_hybrid import load_model

    model, _, cfg = load_model(_model()["model_config"])
    shapes = jax.eval_shape(lambda k: model.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(shapes)) == 822_036_416


def test_traffic_is_one_sequence():
    tr = spec._read_json(os.path.join(
        spec.BENCH_DIR, "traffic", CELL + ".json"))
    assert (tr["family"], tr["kind"], tr["batch"]) == (
        "train_kda_moe", "train_batches", 1)
    assert tr["seq"] in (16384, 32768)          # the second retreat's, or not
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
    # the rate at step 0 is 0, so the parameters may not move at all, and
    # the bias's move is a sign of integer differences
    assert chk["first_step_param_tolerance"] == 0
    assert chk["router_bias_tolerance"] == 0


def test_scope_of_knows_the_new_names():
    path = ("jit(step)/jvp(kda)/kda_rule/while/body/dot_general",
            "jit(step)/jvp(kda)/kda_gate/logistic",
            "jit(step)/jvp(kda)/kda_conv/taps_silu_fwd/pallas_call",
            "jit(step)/jvp(kda_pre_norm)/rsqrt",
            "jit(step)/jvp(mla_out)/attn_gate/mul",
            "jit(step)/jvp(mlp)/moe_shared/dot_general",
            "jit(step)/jvp(mlp)/moe_route/moe_bias_update/sign",
            "jit(step)/jvp(mlp)/rsqrt", "jit(step)/add")
    assert [lib.scope_of(p) for p in path] == [
        "kda_rule", "kda_gate", "kda_conv", "kda_pre_norm", "attn_gate",
        "moe_shared", "moe_route", "mlp", "unscoped"]


@pytest.fixture
def traced_obs(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "trace_dir_of", lambda obs: str(tmp_path))

    def make(kernel_s, model, scope_self_s=None, **train):
        with open(tmp_path / "scopes.json", "w") as f:
            json.dump({"chips": 1, "busy_s": 2.0, "kernel_s": kernel_s,
                       "scope_self_s": {}}, f)
        for name in ("kda_moe_scopes.json", "moe_scopes.json"):
            with open(tmp_path / name, "w") as f:
                json.dump({"chips": 1, "busy_s": 2.0,
                           "scope_self_s": scope_self_s or {}}, f)
        return {"trace": {"busy_s": 2.0, "window_s": 2.01},
                "cell": {"name": CELL}, "model": model,
                "traffic": {"batch": 1, "seq": 16384},
                "device": {"device_kind": "TPU v5 lite"},
                "train": {"traced_steps": 2, "tokens_per_step": 16384,
                          "chips": 1, "untraced_steps": 37, "steps": 37,
                          "window_s": 50.0, "untraced_s": 50.0, **train}}

    return make


_SCOPES = {"kda_in": 0.40, "kda_out": 0.10, "kda_pre_norm": 0.01,
           "kda_conv": 0.07, "kda_gate": 0.07, "kda_rule": 0.60,
           "kda_norm": 0.04, "kda": 0.0, "mla_q": 0.02, "mla_kv": 0.01,
           "mla_rope": 0.01, "mla_out": 0.01, "attn_gate": 0.004,
           "flash": 0.20, "mlp": 0.06, "moe_shared": 0.04,
           "moe_route": 0.10, "moe_dispatch": 0.06, "moe_experts": 0.02,
           "moe_combine": 0.04, "head_loss": 0.06, "embed": 0.02,
           "unscoped": 0.10}


def test_readers_on_a_reduction_with_the_scopes(traced_obs):
    m = _model()
    T = 16384
    obs = traced_obs(
        {"flash_kv_fwd": 0.05, "flash_kv_bwd_dq": 0.07,
         "flash_kv_bwd_dkv": 0.08, "gmm": 0.004, "jvp_jit_tgmm__": 0.006},
        m, scope_self_s=_SCOPES, moe_rows_held=12288.0,
        moe_rows_held_traced=12288.0, moe_rows_routed=6 * T * 8,
        expert_load_max_over_mean=5.0)
    got = {n: spec.metric_reader(n)(obs) for n in NEW + OLD}
    per_token = 6 * T / 197e12
    assert got["kda_proj_roofline"] == pytest.approx(
        100 * per_token * 6 * lib.kda_proj_params(m) / (0.51 / 2))
    floor = max(lib.rule_flops_per_step(m, 1, T) / 197e12,
                lib.rule_bytes_per_step(m, T) / 819e9)
    assert got["kda_rule_roofline"] == pytest.approx(100 * floor / 0.30)
    assert got["kda_conv_roofline"] == pytest.approx(
        100 * (6 * 5 * 12288 * 2 * T / 819e9) / 0.035)
    assert got["kda_gate_share"] == pytest.approx(3.5)
    assert got["mla_flash_roofline.kda_moe"] == pytest.approx(
        100 * lib.flash_flops_per_step(m, 1, T) / 197e12 / 0.10)
    assert got["mla_proj_roofline.kda_moe"] == pytest.approx(
        100 * per_token * lib.mla_proj_params(m) / (0.054 / 2))
    assert got["moe_shared_roofline.kda_moe"] == pytest.approx(
        100 * per_token * 6 * lib.shared_params(m) / 0.02)
    assert got["head_loss_roofline.kda_moe"] == pytest.approx(
        100 * per_token * 2560 * 19648 / 0.03)
    assert got["unscoped_device_share.kda_moe"] == pytest.approx(5.0)
    assert got["moe_route_share.kda_moe"] == pytest.approx(5.0)
    assert got["kda_moe_mfu"] == pytest.approx(
        100 * lib.train_flops_per_step(m, 1, T, 12288) * 37 / 50.0 / 197e12)
    # the readers the benchmark had, on this cell's observations
    assert got["train_tok_per_s_per_chip"] == pytest.approx(37 * T / 50.0)
    assert got["moe_held_row_share"] == pytest.approx(100 * 12288 / (48 * T))
    assert got["moe_held_gmm_roofline"] == pytest.approx(
        100 * 6 * 5_898_240 * 12288 / 197e12 / (0.010 / 2))
    assert got["moe_dispatch_share"] == pytest.approx(10.0)
    assert got["expert_load_max_over_mean"] == 5.0
    assert all(0 < got[n] <= 100 for n in NEW)


def test_readers_find_nothing_in_another_models_run(traced_obs):
    """A program without the new scopes (the parent's, or another cell's),
    and another model: every new reader returns nothing and raises
    nothing."""
    qwen = spec.model_sizes(spec._read_json(os.path.join(
        spec.ROOT, "benchmark/configs/qwen3-next-80b-a3b-c1.json")))
    obs = traced_obs({"flash_kv_fwd": 0.1}, qwen, scope_self_s=_SCOPES)
    assert [spec.metric_reader(n)(obs) for n in NEW] == [None] * len(NEW)
    bare = traced_obs({"flash_fwd": 0.1}, _model(),
                      scope_self_s={"unscoped": 1.0})
    assert [spec.metric_reader(n)(bare) for n in NEW[1:]] == [None] * (
        len(NEW) - 1)
    assert [spec.metric_reader(n)({}) for n in NEW] == [None] * len(NEW)


def test_benchmark_json_holds_the_configuration_and_the_cell():
    """Membership, not the last entry: later PRs append after these."""
    b = spec.load_benchmark()
    (config,) = [c for c in b["configs"] if c["name"] == CONFIG]
    (cell,) = [w for w in b["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, CELL,
                                                                1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    mine = [m for m in b["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == list(NEW)
    assert all(m["moves"] == "train_tok_per_s_per_chip" and m["unit"] == "%"
               for m in mine)
    assert all(os.path.exists(os.path.join(
        spec.BENCH_DIR, "metrics", m["name"] + ".py")) for m in mine)
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


def test_a_last_bit_tie_of_two_groups_is_kept_either_way_round():
    """The chip's sigmoid and the host's differ in a last bit: where two
    groups' scores lie that near at the edge of the kept ones, the host may
    keep the other group, and the choices the program made in the groups
    both keep then fall below the host's k-th (a reading of 3.7e-4 at 1 x
    32,768, PR 60). ``regrets(ties=)`` reads such a token under the keeping
    that suits its choices, and still sees a fault there."""
    import types

    import numpy as np

    from benchmark.cells import train_kda_moe as cell

    cfg = types.SimpleNamespace(n_group=4, topk_group=2, top_k=3)
    bit = np.float32(2.0 ** -23)
    select = np.full((1, 1, 16), 0.125, np.float32)
    select[0, 0, 0:3] = [0.875, 0.5625, 0.25]           # kept by both
    select[0, 0, 4:6] = [0.75, 0.5]                     # the program's
    select[0, 0, 8:10] = [0.625, 0.625 + bit]           # the host's, by a bit
    chosen = np.array([[[0, 4, 1]]])
    assert cell.regrets(cfg, select, chosen).max() == 0.0625
    assert cell.regrets(cfg, select, chosen, cell.GROUP_TIE).max() == 0.0
    # and the host's own keeping passes too
    assert cell.regrets(cfg, select, np.array([[[0, 8, 9]]]),
                        cell.GROUP_TIE).max() <= 0
    # a choice no keeping makes is seen at the tie as anywhere
    assert cell.regrets(cfg, select, np.array([[[0, 4, 2]]]),
                        cell.GROUP_TIE).max() == 0.3125
    # no tie: nothing is forgiven
    select[0, 0, 9] = 0.6875
    assert cell.regrets(cfg, select, chosen, cell.GROUP_TIE).max() == 0.0625
