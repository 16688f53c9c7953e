"""What PR 56 added for ``train-keye-vl2-1chip``: the cell end to end at a
tiny size on a CPU worker, its generator's documents against the rule
written out, and the new readers on a reduction that has the scopes and on
one that lacks them (a program of another model). The FLOP and byte
functions against hand counts are in ``tests/test_keye_vl2.py`` (tier-1)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.generators import train_batches_mrope as gen
from benchmark.lib import scopes, sparse_gqa_flops as sg, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "train-keye-vl2-1chip"
NEW = ("sparse_gqa_mfu", "dsa_flash_roofline.gqa", "dsa_index_roofline.gqa",
       "attn_proj_roofline.sparse_gqa", "head_loss_roofline.sparse_gqa",
       "unscoped_device_share.sparse_gqa", "mrope_share")
APPENDED = ("dsa_select_share", "moe_dispatch_share",
            "expert_load_max_over_mean", "moe_held_gmm_roofline",
            "moe_held_row_share")


def test_cell_runs_tiny_on_cpu():
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_sparse_gqa.py")],
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"train_tok_per_s_per_chip", "setup_s"}
    assert "compilations inside the window: 0" in p.stdout
    assert "differing keys, share: 0.000e+00" in p.stdout
    assert "chosen keys, count: 0.000e+00" in p.stdout
    assert "first step, moment, layer_1" in p.stdout
    assert "first-step balancing term" in p.stdout
    assert "of 576 routed rows a step the held experts multiplied" \
        in p.stdout


def test_the_cell_is_what_the_issue_names():
    bench = spec.load_benchmark()
    ctx = spec.resolve_cell(bench, CELL)
    assert ctx["cell"]["chips"] == 1
    assert len(bench["workloads"]) == 12
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    tr = ctx["traffic"]
    assert (tr["family"], tr["kind"], tr["batch"], tr["seq"]) == (
        "train_sparse_gqa", "train_batches_mrope", 1, 16_384)
    assert (tr["lr"], tr["lr_warmup_steps"], tr["host_batches"]) == (
        1e-4, 2000, 64)
    assert (tr["text_run"], tr["image_share"]) == ([64, 1024], 0.5)
    assert tr["grids"] == [[16, 16], [24, 24], [32, 32]]
    names = {m["name"] for m in ctx["per_layer"]}
    assert set(NEW) | set(APPENDED) <= names
    assert {"host_ms_per_step", "device_idle_share.train",
            "setup_compile_s", "loop_wait_ms_p50"} <= names
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_tok_per_s_per_chip"
    m = ctx["config"]
    assert m["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert m["reduced_from"] == {"num_hidden_layers": 48, "num_experts": 128,
                                 "vocab_size": 151_936}
    assert (m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"],
            m["moe_intermediate_size"], m["num_experts_per_tok"],
            m["num_local_experts"]) == (2048, 32, 4, 128, 768, 8, 128)
    assert m["sa_config"]["topk"] == m["index_topk"] == 2048
    assert m["rope_scaling"]["mrope_section"] == [16, 24, 24]


def test_a_documents_positions_follow_the_rule():
    """Text runs on in all three streams; an image span of ``gh x gw`` at
    ``p`` holds ``p``, ``p + row``, ``p + col`` and the next token stands
    at ``p + max(gh, gw)``; half the positions are image positions, and
    the mask is 0 exactly there."""
    tr = spec.resolve_cell(spec.load_benchmark(), CELL)["traffic"]
    doc = gen.document(np.random.default_rng(5), tr["seq"] + 1, tr)
    pos, image = doc["positions"], doc["image"]
    assert pos.shape == (3, tr["seq"] + 1) and 0.4 < image.mean() < 0.6
    nxt = 0
    for kind, at, n, *grid in doc["spans"]:
        if kind == "text":
            assert 1 <= n <= 1024 and not image[at:at + n].any()
            assert (pos[:, at:at + n] == nxt + np.arange(n)).all()
            nxt += n
            continue
        gh, gw = grid
        assert n == gh * gw and [gh, gw] in tr["grids"]
        assert image[at:at + n].all()
        rows, cols = np.divmod(np.arange(n), gw)
        assert (pos[0, at:at + n] == nxt).all()
        assert (pos[1, at:at + n] == nxt + rows).all()
        assert (pos[2, at:at + n] == nxt + cols).all()
        nxt += max(gh, gw)
    assert sum(s[2] for s in doc["spans"]) == tr["seq"] + 1
    host = gen.host_batches({**tr, "host_batches": 2}, 2 ** 31 + 9, 37_984)
    assert host["tokens"].shape == (2, 1, tr["seq"] + 1)
    assert host["positions"].shape == (2, 3, 1, tr["seq"])
    assert host["mask"].shape == (2, 1, tr["seq"] + 1)
    assert 0 <= host["tokens"].min() and host["tokens"].max() < 37_984
    again = gen.host_batches({**tr, "host_batches": 2}, 2 ** 31 + 9, 37_984)
    assert all((host[k] == again[k]).all() for k in host)


def _model():
    return spec.model_sizes(spec._read_json(os.path.join(
        spec.ROOT, "benchmark/configs/keye-vl-2.0-30b-a3b-c1.json")))


@pytest.fixture
def traced_obs(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "trace_dir_of", lambda obs: str(tmp_path))

    def make(kernel_s, model, scope_self_s=None, **train):
        with open(tmp_path / "scopes.json", "w") as f:
            json.dump({"chips": 1, "busy_s": 1.0, "kernel_s": kernel_s,
                       "scope_self_s": {}}, f)
        for name in ("sparse_gqa_scopes.json", "sparse_scopes.json",
                     "moe_scopes.json"):
            with open(tmp_path / name, "w") as f:
                json.dump({"chips": 1, "busy_s": 1.0,
                           "scope_self_s": scope_self_s or {}}, f)
        return {"trace": {"busy_s": 1.0}, "cell": {"name": CELL},
                "model": model, "traffic": {"batch": 1, "seq": 16384},
                "device": {"device_kind": "TPU v5 lite"},
                "train": {"traced_steps": 2, "tokens_per_step": 16384,
                          "chips": 1, "untraced_steps": 10,
                          "untraced_s": 20.0, **train}}

    return make


def test_readers_on_a_reduction_with_the_scopes(traced_obs):
    m = _model()
    obs = traced_obs(
        {"gmm": 0.02, "jvp_jit_gmm__": 0.02},
        m, scope_self_s={
            "attn_qkv": 0.1, "attn_out": 0.03, "dsa_proj": 0.03,
            "dsa_scores": 0.16, "dsa_select": 0.3, "flash_sparse": 1.2,
            "dsa_loss": 0.05, "mrope": 0.002, "mlp": 0.01, "head_loss": 0.1,
            "moe_route": 0.02, "moe_dispatch": 0.05, "moe_combine": 0.03,
            "moe_experts": 0.05, "unscoped": 0.2},
        moe_rows_routed=786_432, moe_rows_held=196_608.0,
        moe_rows_held_traced=200_000.0)
    got = {n: spec.metric_reader(n)(obs) for n in NEW}
    peak = 197e12
    assert got["dsa_index_roofline.gqa"] == pytest.approx(
        100 * sg.index_flops_per_step(m, 1, 16384) / peak / 0.08)
    assert got["dsa_flash_roofline.gqa"] == pytest.approx(
        100 * sg.sparse_flash_flops_per_step(m, 1, 16384) / peak / 0.6)
    assert got["attn_proj_roofline.sparse_gqa"] == pytest.approx(
        100 * sg.proj_flops_per_step(m, 16384) / peak / 0.08)
    assert got["head_loss_roofline.sparse_gqa"] == pytest.approx(
        100 * 6 * 16384 * 2048 * 37_984 / peak / 0.05)
    assert got["unscoped_device_share.sparse_gqa"] == pytest.approx(20.0)
    assert got["mrope_share"] == pytest.approx(0.2)
    assert got["sparse_gqa_mfu"] == pytest.approx(
        100 * sg.train_flops_per_step(m, 1, 16384, 196_608.0)
        * 10 / 20.0 / peak)
    assert all(0 < v <= 100 for v in got.values())
    # the accepted readers the cell was appended to read it as they stand
    assert spec.metric_reader("dsa_select_share")(obs) == pytest.approx(30.0)
    assert spec.metric_reader("moe_dispatch_share")(obs) == pytest.approx(
        10.0)
    assert spec.metric_reader("moe_held_gmm_roofline")(obs) == pytest.approx(
        100 * 6 * 200_000 * 3 * 2048 * 768 / peak / 0.02)
    assert spec.metric_reader("moe_held_row_share")(obs) == pytest.approx(
        25.0)


def test_readers_find_nothing_in_another_models_run(traced_obs):
    """A program without the scopes (the parent's, or another cell's):
    every new reader returns nothing and raises nothing."""
    other = spec.model_sizes(spec._read_json(os.path.join(
        spec.ROOT, "benchmark/configs/dots3-note-prev-c1.json")))
    obs = traced_obs({"gmm": 0.1}, other,
                     scope_self_s={"mlp": 0.3, "head_loss": 0.1,
                                   "dsa_scores": 0.1, "flash_sparse": 0.2},
                     moe_rows_routed=1, moe_rows_held=1.0)
    assert [spec.metric_reader(n)(obs) for n in NEW] == [None] * len(NEW)
    assert [spec.metric_reader(n)({}) for n in NEW] == [None] * len(NEW)
    # this model's trace without the scopes (a CPU rehearsal, or a program
    # from before them)
    obs = traced_obs({}, _model(), scope_self_s={"mlp": 0.3})
    for n in NEW[1:]:
        assert spec.metric_reader(n)(obs) is None, n
