"""What PR 43 added for ``train-deepseek-v2-1chip``: the cell end to end
at a tiny size on a CPU worker, its check's regret on written-out cases,
and the new readers on a reduction that has latent attention's calls and
scopes and on one that lacks them (a program of another model). The FLOP
and byte functions against hand counts are in ``tests/test_deepseek_v2.py``
(tier-1)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.lib import latent_flops, scopes, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "train-deepseek-v2-1chip"
NEW = ("latent_mfu", "mla_flash_roofline", "mla_proj_roofline",
       "mlp_roofline.latent", "head_loss_roofline.latent",
       "unscoped_device_share.latent")


def test_cell_runs_tiny_on_cpu():
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_latent.py")],
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"train_tok_per_s_per_chip", "setup_s"}
    assert "compilations inside the window: 0" in p.stdout
    assert "differing choices, share: 0.000e+00" in p.stdout
    assert "first step, moment, mla_moe" in p.stdout
    assert "of 384 routed rows a step the held experts multiplied" \
        in p.stdout


def test_choice_regret_on_written_out_cases():
    """Eight experts in four groups of two, two kept, two a token."""
    from benchmark.cells.train_latent import choice_regret

    ref = np.asarray([[[3.0, 0.0, 2.0, 1.9, 1.95, 0.0, -1.0, -1.0]]])
    # the reference keeps groups 0 and 1 (best 3.0 and 2.0) and takes
    # experts 0 and 2

    def regret(chosen, got=ref):
        return choice_regret(ref, got, np.asarray([[chosen]]), 4, 2,
                             2)[0, 0].tolist()

    assert regret([0, 2]) == [0.0, 0.0]
    # inside the kept groups, the third best for the second: its gap
    assert regret([0, 3]) == pytest.approx([0.0, 0.1])
    # the program's own logits put group 2 ahead of group 1 by a rounding:
    # its choice of expert 4 is a near-tie of the groups' scores
    got = ref.copy()
    got[0, 0, 4] = 2.01
    assert regret([0, 4], got) == pytest.approx([0.0, 0.05])
    # no group limit: a choice outside the program's own groups counts by
    # its group's distance from the reference's last kept group
    assert regret([0, 6]) == pytest.approx([0.0, 3.0])


def _model():
    return spec.model_sizes(spec._read_json(os.path.join(
        spec.ROOT, "benchmark/configs/deepseek-v2-c1.json")))


@pytest.fixture
def traced_obs(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "trace_dir_of", lambda obs: str(tmp_path))

    def make(kernel_s, model, latent_scope_self_s=None, **train):
        with open(tmp_path / "scopes.json", "w") as f:
            json.dump({"chips": 1, "busy_s": 1.0, "kernel_s": kernel_s,
                       "scope_self_s": {}}, f)
        with open(tmp_path / "latent_scopes.json", "w") as f:
            json.dump({"chips": 1, "busy_s": 1.0,
                       "scope_self_s": latent_scope_self_s or {}}, f)
        return {"trace": {"busy_s": 1.0}, "cell": {"name": CELL},
                "model": model, "traffic": {"batch": 1, "seq": 8192},
                "device": {"device_kind": "TPU v5 lite"},
                "train": {"traced_steps": 2, "tokens_per_step": 8192,
                          "chips": 1, "untraced_steps": 10,
                          "untraced_s": 5.0, **train}}

    return make


def test_readers_on_a_reduction_with_the_calls(traced_obs):
    m = _model()
    obs = traced_obs(
        {"flash_kv_fwd": 0.06, "flash_kv_bwd_dq": 0.04,
         "flash_kv_bwd_dkv": 0.06, "flash_fwd": 9.0, "gmm": 0.02},
        m, latent_scope_self_s={
            "mla_q": 0.05, "mla_kv": 0.03, "mla_rope": 0.01, "mla_out": 0.03,
            "mlp": 0.25, "head_loss": 0.05, "moe_experts": 0.05,
            "unscoped": 0.2},
        moe_rows_routed=196608, moe_rows_held=9800.0,
        moe_rows_held_traced=9900.0)
    got = {n: spec.metric_reader(n)(obs) for n in NEW}
    flash = latent_flops.flash_flops_per_step(m, 1, 8192) / 197e12
    assert got["mla_flash_roofline"] == pytest.approx(100 * flash / 0.08)
    per_token = 6 * 8192 / 197e12
    assert got["mla_proj_roofline"] == pytest.approx(
        100 * per_token * 5 * 45_416_448 / 0.06)
    assert got["mlp_roofline.latent"] == pytest.approx(
        100 * per_token * 3 * 5120 * (12288 + 4 * 3072) / 0.125)
    assert got["head_loss_roofline.latent"] == pytest.approx(
        100 * per_token * 5120 * 12800 / 0.025)
    assert got["unscoped_device_share.latent"] == pytest.approx(20.0)
    assert got["latent_mfu"] == pytest.approx(
        100 * latent_flops.train_flops_per_step(m, 1, 8192, 9800.0)
        * 10 / 5.0 / 197e12)
    assert all(0 < v <= 100 for v in got.values())
    # the readers of the held share read this cell as they stand
    assert spec.metric_reader("moe_held_gmm_roofline")(obs) == pytest.approx(
        100 * 6 * 9900 * 3 * 5120 * 1536 / 197e12 / 0.01)
    assert spec.metric_reader("moe_held_row_share")(obs) == pytest.approx(
        100 * 9800 / 196608)


def test_readers_find_nothing_in_another_models_run(traced_obs):
    """A program without latent attention's calls and scopes (the
    parent's, or another cell's): every reader returns nothing and raises
    nothing."""
    laguna = spec.model_sizes(spec._read_json(os.path.join(
        spec.ROOT, "benchmark/configs/laguna-s-2.1-c1.json")))
    obs = traced_obs({"flash_fwd": 0.1, "gmm": 0.1}, laguna,
                     latent_scope_self_s={"mlp": 0.3, "head_loss": 0.1},
                     moe_rows_routed=1, moe_rows_held=1.0)
    assert [spec.metric_reader(n)(obs) for n in NEW] == [None] * len(NEW)
    assert [spec.metric_reader(n)({}) for n in NEW] == [None] * len(NEW)
    # this model's trace without the calls (a CPU rehearsal)
    obs = traced_obs({}, _model(), latent_scope_self_s={"mlp": 0.3})
    assert spec.metric_reader("mla_flash_roofline")(obs) is None
    assert spec.metric_reader("mla_proj_roofline")(obs) is None
