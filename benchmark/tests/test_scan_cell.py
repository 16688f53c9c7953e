"""What PR 36 added for ``train-granite-1chip``: the cell end to end at a
tiny size on a CPU worker, the FLOP and byte functions by layer kind
against hand counts, and the new readers on a reduction that has the
scan's scopes and on one that lacks them (a program of another model, or
the parent's)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import scan_flops, scopes, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "train-granite-1chip"
NEW = ("scan_mfu", "ssm_proj_roofline", "ssd_scan_roofline",
       "ssm_conv_roofline", "flash_attn_roofline.scan", "mlp_roofline.scan",
       "head_loss_roofline.scan", "unscoped_device_share.scan",
       "attn_proj_roofline.scan")
# readers the benchmark had, which the cell is appended to
OLD = ("train_tok_per_s_per_chip", "host_ms_per_step")


def test_cell_runs_tiny_on_cpu():
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_scan.py")],
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
                 "gradient, mamba", "gradient, attention", "gradient, top",
                 "first step, moment, mamba", "first step, moment, attention",
                 "first step, moment, top", "first step, parameters"):
        assert f"[bench] {what}: " in p.stdout, what
    assert "ok=False" not in p.stdout


def test_the_parent_fails_at_once_without_the_model(monkeypatch, tmp_path):
    """A checkout from before ``ray_tpu/models/granite.py``: ``run``
    raises before it starts a runtime or a worker."""
    from benchmark.cells import train_scan

    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    with pytest.raises(RuntimeError, match="no ray_tpu/models/granite.py"):
        train_scan.run({"model_config": {"module": "granite"}})


def _model():
    return spec.model_sizes(spec._read_json(os.path.join(
        spec.ROOT, "benchmark/configs/granite-4.0-h-micro-c1.json")))


def test_config_states_every_published_width():
    m = _model()
    assert (m["hidden_size"], m["intermediate_size"],
            m["shared_intermediate_size"]) == (2048, 8192, 8192)
    assert (m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"]) == (32, 8, 64)
    assert (m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"],
            m["mamba_n_groups"], m["mamba_d_conv"], m["mamba_chunk_size"],
            m["mamba_expand"]) == (64, 64, 128, 1, 4, 256, 2)
    assert (m["embedding_multiplier"], m["attention_multiplier"],
            m["residual_multiplier"], m["logits_scaling"]) == (
        12, 0.015625, 0.22, 8)
    assert m["vocab_size"] == 100352 and m["tie_word_embeddings"] is True
    assert m["position_embedding_type"] == "nope"
    assert m["reduced"] == ["num_hidden_layers"]
    assert m["reduced_from"] == {"num_hidden_layers": 40}
    assert len(m["layer_types"]) == 40
    assert [m["layer_types"][l] for l in m["held"]["layers"]] == \
        m["held"]["layer_kinds"]
    assert m["held"]["layers"] == list(range(m["num_hidden_layers"]))
    mc = m["model_config"]
    assert mc["attention_layers"] == [k == "attention"
                                      for k in m["held"]["layer_kinds"]]
    for hf, ours in (("mamba_n_heads", "ssm_heads"),
                     ("mamba_d_head", "ssm_head_dim"),
                     ("mamba_d_state", "ssm_state"),
                     ("mamba_n_groups", "ssm_groups"),
                     ("mamba_d_conv", "ssm_conv_taps"),
                     ("mamba_chunk_size", "ssm_chunk"),
                     ("embedding_multiplier", "embedding_multiplier"),
                     ("attention_multiplier", "attention_multiplier"),
                     ("residual_multiplier", "residual_multiplier"),
                     ("logits_scaling", "logits_scaling")):
        assert m[hf] == mc[ours], hf
    # the catalog's row, key for key but the depth
    row = next(json.loads(l) for l in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"granite-4.0-h-micro"' in l) if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else None
    if row:
        assert m["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if m.get(k) != v} == {
            "num_hidden_layers"}


def test_traffic_is_one_sequence_of_32768():
    tr = spec._read_json(os.path.join(
        spec.BENCH_DIR, "traffic", CELL + ".json"))
    assert (tr["family"], tr["batch"], tr["seq"]) == ("train_scan", 1, 32768)
    assert (tr["lr"], tr["lr_warmup_steps"]) == (0.0001, 2000)
    chk = tr["check"]
    assert set(chk["gradient_gap_tolerance"]) == {"mamba", "attention",
                                                  "top"}
    for key in ("loss_tolerance", "token_nll_rms_tolerance",
                "token_nll_max_tolerance", "state_abs_max_tolerance",
                "state_head_gap_tolerance"):
        assert 0 < chk[key] < 0.5, key
    assert all(0 < v < 0.5 for v in chk["gradient_gap_tolerance"].values())
    # a step that hands on what it was given reads 1 on the moment; the
    # rate at step 0 is 0, so the parameters may not move at all
    assert set(chk["first_step_moment_tolerance"]) == {"mamba", "attention",
                                                       "top"}
    assert all(0 < v < 0.5
               for v in chk["first_step_moment_tolerance"].values())
    assert chk["first_step_param_tolerance"] == 0


def test_flops_and_bytes_against_hand_counts():
    m = _model()
    assert scan_flops.count(m, "mamba") == 9
    assert scan_flops.count(m, "attention") == 1
    proj = 2048 * 8512 + 4096 * 2048                        # 25.82 M
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512                 # 10.49 M
    assert scan_flops.ssm_conv_dim(m) == 4352
    assert scan_flops.ssm_proj_params(m) == proj
    assert scan_flops.attn_proj_params(m) == attn
    assert scan_flops.mlp_params(m) == 10 * 3 * 2048 * 8192
    assert scan_flops.head_params(m) == 2048 * 100352
    assert scan_flops.token_matmul_params(m) == (
        9 * proj + attn + 10 * 3 * 2048 * 8192 + 2048 * 100352)
    # every parameter but norms, taps, biases and the scan's scalars
    assert 951_991_232 - scan_flops.token_matmul_params(m) == (
        9 * (2 * 2048 + 4096 + 4352 * 5 + 3 * 64) + 2 * 2048 + 2048)
    T = 32768
    assert scan_flops.attention_flops_fwd(m, 1, T) == \
        32 * 4 * 64 * (T * (T + 1) / 2)
    pairs = 256 * 257 / 2
    fwd = 128 * (2 * pairs * (128 + 4096) + 4 * 256 * 64 * 64 * 128)
    assert scan_flops.scan_flops_fwd(m, 1, T) == fwd
    assert scan_flops.scan_flops_per_step(m, 1, T) == 3 * 9 * fwd
    ins, out = (4096 + 256) * 2 + 64 * 4, 4096 * 2
    assert scan_flops.scan_bytes_per_step(m, T) == \
        9 * T * (ins + out + 2 * ins + out)
    assert scan_flops.conv_bytes_per_step(m, T) == 9 * 5 * 4352 * 2 * T
    # a layer's scan forward at the chip's peaks: 0.53 ms of FLOPs, 0.69
    # ms of bytes (the issue: "each about 0.7 ms")
    assert fwd / 197e12 == pytest.approx(0.53e-3, rel=0.01)
    assert T * (ins + out) / 819e9 == pytest.approx(0.69e-3, rel=0.01)
    # the issue's count: about 206 T a step, 6.3 G a token
    step = scan_flops.train_flops_per_step(m, 1, T)
    assert abs(step / 206e12 - 1) < 0.02
    share = lambda f: round(100 * f / step, 1)
    assert share(6 * scan_flops.mlp_params(m) * T) == 48.7
    assert share(6 * 9 * proj * T) == 22.5
    assert share(6 * scan_flops.head_params(m) * T) == 19.9
    assert share(scan_flops.scan_flops_per_step(m, 1, T)) == 1.4


def test_scope_of_knows_the_scans_names():
    path = ("jit(step)/jvp(ssm)/ssm_scan/while/body/checkpoint/dot_general",
            "jit(step)/transpose(jvp(ssm))/ssm_conv/mul",
            "jit(step)/jvp(ssm)/ssm_in/dot_general",
            "jit(step)/transpose(jvp(ssm))/ssm_norm/rsqrt",
            "jit(step)/jvp(ssm)/reshape", "jit(step)/jvp(mlp)/dot_general",
            "jit(step)/add")
    assert [scan_flops.scope_of(p) for p in path] == [
        "ssm_scan", "ssm_conv", "ssm_in", "ssm_norm", "ssm", "mlp",
        "unscoped"]
    # the readers the benchmark had send the scan's time to unscoped
    assert scopes.scope_of(path[0]) == "unscoped"


@pytest.fixture
def traced_obs(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "trace_dir_of", lambda obs: str(tmp_path))

    def make(kernel_s, model, scan_scope_self_s=None, **train):
        with open(tmp_path / "scopes.json", "w") as f:
            json.dump({"chips": 1, "busy_s": 4.0, "kernel_s": kernel_s,
                       "scope_self_s": {}}, f)
        with open(tmp_path / "scan_scopes.json", "w") as f:
            json.dump({"chips": 1, "busy_s": 4.0,
                       "scope_self_s": scan_scope_self_s or {}}, f)
        return {"trace": {"busy_s": 4.0, "window_s": 4.02},
                "cell": {"name": CELL}, "model": model,
                "traffic": {"batch": 1, "seq": 32768},
                "device": {"device_kind": "TPU v5 lite"},
                "train": {"traced_steps": 2, "tokens_per_step": 32768,
                          "chips": 1, "untraced_steps": 10, "steps": 12,
                          "window_s": 26.0, "untraced_s": 21.0, **train}}

    return make


def test_readers_on_a_reduction_with_the_scopes(traced_obs):
    m = _model()
    obs = traced_obs(
        {"flash_fwd": 0.10, "flash_bwd_dq": 0.07, "flash_bwd_dkv": 0.11}, m,
        scan_scope_self_s={
            "ssm_in": 0.44, "ssm_out": 0.16, "ssm_conv": 0.16,
            "ssm_scan": 0.80, "ssm_norm": 0.14, "ssm": 0.01,
            "attn_qkv": 0.02, "attn_out": 0.01, "flash": 0.30, "mlp": 1.40,
            "head_loss": 0.62, "embed": 0.02, "unscoped": 0.40})
    got = {n: spec.metric_reader(n)(obs) for n in NEW + OLD}
    per_token = 6 * 32768 / 197e12
    assert got["ssm_proj_roofline"] == pytest.approx(
        100 * per_token * 9 * (2048 * 8512 + 4096 * 2048) / 0.30)
    floor = max(scan_flops.scan_flops_per_step(m, 1, 32768) / 197e12,
                scan_flops.scan_bytes_per_step(m, 32768) / 819e9)
    assert floor == scan_flops.scan_bytes_per_step(m, 32768) / 819e9
    assert got["ssd_scan_roofline"] == pytest.approx(100 * floor / 0.40)
    assert got["ssm_conv_roofline"] == pytest.approx(
        100 * (9 * 5 * 4352 * 2 * 32768 / 819e9) / 0.08)
    assert got["flash_attn_roofline.scan"] == pytest.approx(
        100 * scan_flops.flash_flops_per_step(m, 1, 32768) / 197e12 / 0.14)
    assert got["mlp_roofline.scan"] == pytest.approx(
        100 * per_token * 10 * 3 * 2048 * 8192 / 0.70)
    assert got["head_loss_roofline.scan"] == pytest.approx(
        100 * per_token * 2048 * 100352 / 0.31)
    assert got["attn_proj_roofline.scan"] == pytest.approx(
        100 * per_token * (2 * 2048 * 2048 + 2 * 2048 * 512) / 0.015)
    assert got["unscoped_device_share.scan"] == pytest.approx(10.0)
    assert got["scan_mfu"] == pytest.approx(
        100 * scan_flops.train_flops_per_step(m, 1, 32768) * 10 / 21.0
        / 197e12)
    # the readers the benchmark had, on this cell's observations
    assert got["train_tok_per_s_per_chip"] == pytest.approx(
        12 * 32768 / 26.0)
    assert got["host_ms_per_step"] == pytest.approx(10.0)
    assert all(0 < got[n] <= 100 for n in NEW)


def test_readers_find_nothing_in_another_models_run(traced_obs):
    """A program without the scan's scopes (the parent's, or another
    cell's), and a model without scan layers: every new reader returns
    nothing and raises nothing."""
    lfm2 = spec.model_sizes(spec._read_json(os.path.join(
        spec.ROOT, "benchmark/configs/lfm2-8b-a1b-c1.json")))
    obs = traced_obs({"flash_fwd": 0.1}, lfm2,
                     scan_scope_self_s={"attn_qkv": 0.1, "attn_out": 0.1,
                                        "mlp": 0.3, "head_loss": 0.1})
    assert [spec.metric_reader(n)(obs) for n in NEW] == [None] * len(NEW)
    # this model's cell, run on a program that names none of the scopes
    bare = traced_obs({"flash_fwd": 0.1}, _model(),
                      scan_scope_self_s={"unscoped": 1.0})
    got = {n: spec.metric_reader(n)(bare) for n in NEW}
    assert {n for n, v in got.items() if v is not None} <= {
        "flash_attn_roofline.scan", "scan_mfu"}
    assert [spec.metric_reader(n)({}) for n in NEW] == [None] * len(NEW)


def test_benchmark_json_appends_one_configuration_and_one_cell():
    b = spec.load_benchmark()
    assert [c["name"] for c in b["configs"]][-1] == "granite-4.0-h-micro-c1"
    assert [w["name"] for w in b["workloads"]][-1] == CELL
    assert len(b["configs"]) == len(b["workloads"]) == 6
    cell = b["workloads"][-1]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
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
