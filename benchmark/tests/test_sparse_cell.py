"""What PR 46 added for ``train-dots3-1chip``: the cell end to end at a
tiny size on a CPU worker, its check's regrets, counts and band probe on
written-out cases, and the new readers on a reduction that has the index's
scopes and the window kernels' calls and on one that lacks them (a program
of another model). The FLOP and byte functions against hand counts are in
``tests/test_dots3.py`` (tier-1)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.lib import scopes, sparse_flops, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "train-dots3-1chip"
NEW = ("sparse_mfu", "dsa_index_roofline", "dsa_select_share",
       "dsa_flash_roofline", "mla_window_roofline",
       "mla_proj_roofline.sparse", "mlp_roofline.sparse",
       "head_loss_roofline.sparse", "unscoped_device_share.sparse")


def test_cell_runs_tiny_on_cpu():
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_sparse.py")],
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"train_tok_per_s_per_chip", "setup_s"}
    assert "compilations inside the window: 0" in p.stdout
    assert "differing keys, share: 0.000e+00" in p.stdout
    assert "chosen keys, count: 0.000e+00" in p.stdout
    assert "first step, router biases: 0.000e+00" in p.stdout
    assert "first step, moment, sliding_moe" in p.stdout
    assert "of 864 routed rows a step the held experts multiplied" \
        in p.stdout


def test_the_cell_is_what_the_issue_names():
    bench = spec.load_benchmark()
    ctx = spec.resolve_cell(bench, CELL)
    assert ctx["cell"]["chips"] == 1
    tr = ctx["traffic"]
    assert (tr["family"], tr["batch"], tr["seq"]) == ("train_sparse", 1,
                                                      16_384)
    assert (tr["lr"], tr["lr_warmup_steps"], tr["host_batches"]) == (
        1e-4, 2000, 64)
    names = {m["name"] for m in ctx["per_layer"]}
    assert set(NEW) <= names
    assert {"moe_held_gmm_roofline", "moe_held_row_share",
            "moe_dispatch_share", "expert_load_max_over_mean",
            "host_ms_per_step", "device_idle_share.train",
            "setup_compile_s"} <= names
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_tok_per_s_per_chip"


def test_expert_regret_on_written_out_cases():
    """Four experts, two a token, in the selection scores sigmoid + b."""
    from benchmark.cells.train_sparse import expert_regret

    logits = np.asarray([[[2.0, 1.0, 0.9, -3.0]]])
    none = np.zeros((1, 4))

    def regret(chosen, bias=none):
        return expert_regret(logits, bias, np.asarray([[chosen]]),
                             2)[0, 0].tolist()

    sig = 1 / (1 + np.exp(-logits[0, 0]))
    assert regret([0, 1]) == [0.0, 0.0]
    assert regret([0, 2]) == pytest.approx([0.0, sig[1] - sig[2]])
    assert regret([0, 3]) == pytest.approx([0.0, sig[1] - sig[3]])
    # a bias that lifts expert 2 over expert 1 makes it the reference's own
    assert regret([0, 2], np.asarray([[0.0, 0.0, 0.1, 0.0]])) == [0.0, 0.0]


def test_key_gaps_count_what_the_reference_would_not_choose():
    """Eight positions, six index heads, three keys a query: a program
    that chooses the reference's own keys reads no regret and the right
    count; one that takes the lowest score instead of the third reads
    that key's distance, and one that chooses too few reads a count."""
    import jax
    import jax.numpy as jnp
    from types import SimpleNamespace

    from benchmark.cells import train_sparse
    from benchmark.references import dots3_ref as ref

    S = 8
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    q_i = jax.random.normal(k[0], (S, 6, 4))
    k_i = jax.random.normal(k[1], (S, 4))
    w = jax.random.normal(k[2], (S, 6))
    scores = np.asarray(ref.index_scores(q_i, k_i, w))
    own = np.asarray(ref.plain_top_k(jnp.asarray(scores), 0, 3))
    cfg = SimpleNamespace(index_topk=3)

    def gaps(choice):
        packed = jnp.asarray(np.packbits(choice, axis=-1))[None, None]
        return train_sparse.key_gaps(
            ref, cfg, [[(q_i, k_i, w)]],
            (q_i[None, None], k_i[None, None], w[None, None]), packed)

    got = gaps(own)
    assert got["keys"] == {"differing_share": 0.0, "max_regret": 0.0,
                           "count": 1 + 2 + 6 * 3, "count_gap": 0}
    assert got["index_score_gap"]["pairs"] == 36
    assert got["index_score_gap"]["max"] < 1e-5
    # position 7: its lowest causal score for its third largest
    row = np.where(np.arange(S) <= 7, scores[7], np.inf)
    third = np.sort(scores[7])[-3]
    swapped = own.copy()
    swapped[7, np.flatnonzero(own[7] & (scores[7] == third))[0]] = False
    swapped[7, int(row.argmin())] = True
    got = gaps(swapped)
    assert got["keys"]["differing_share"] == pytest.approx(1 / 21)
    assert got["keys"]["max_regret"] == pytest.approx(third - row.min(),
                                                      rel=1e-5)
    assert got["keys"]["count_gap"] == 0
    fewer = own.copy()
    fewer[7, int(np.flatnonzero(own[7])[0])] = False
    assert gaps(fewer)["keys"]["count_gap"] == 1


@pytest.mark.parametrize("planted,reads", [(5, 0.0), (4, 1.0), (6, 5 / 6)])
def test_band_probe_reads_the_windows_width(planted, reads):
    """The probe on ``tiny()``'s window layers (values of 12, a band of
    5), in keys: nothing at the stated width; one short, the dropped key;
    one long, the added key's share of 1/6 of a band of 5."""
    from dataclasses import replace

    from benchmark.cells import train_sparse
    from ray_tpu.models.dots3 import Dots3Config

    cfg = Dots3Config.tiny(attn_impl="reference")
    got = train_sparse.band_gap(replace(cfg, sliding_window=planted), cfg, 48)
    assert got == pytest.approx(reads, abs=1e-5)


def _model():
    return spec.model_sizes(spec._read_json(os.path.join(
        spec.ROOT, "benchmark/configs/dots3-note-prev-c1.json")))


@pytest.fixture
def traced_obs(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "trace_dir_of", lambda obs: str(tmp_path))

    def make(kernel_s, model, scope_self_s=None, **train):
        with open(tmp_path / "scopes.json", "w") as f:
            json.dump({"chips": 1, "busy_s": 1.0, "kernel_s": kernel_s,
                       "scope_self_s": {}}, f)
        with open(tmp_path / "sparse_scopes.json", "w") as f:
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
        {"flash_kv_fwd": 0.02, "flash_kv_bwd_dq": 0.02,
         "flash_kv_bwd_dkv": 0.02, "gmm": 0.02},
        m, scope_self_s={
            "mla_q": 0.05, "mla_kv": 0.03, "mla_rope": 0.01, "mla_out": 0.03,
            "attn_gate": 0.01, "dsa_proj": 0.03, "dsa_scores": 0.8,
            "dsa_select": 0.3, "flash_sparse": 0.6, "dsa_loss": 0.2,
            "flash_window": 0.06, "mlp": 0.4, "moe_shared": 0.1,
            "head_loss": 0.1, "moe_experts": 0.05, "unscoped": 0.2},
        moe_rows_routed=524288, moe_rows_held=16400.0,
        moe_rows_held_traced=16500.0)
    got = {n: spec.metric_reader(n)(obs) for n in NEW}
    peak = 197e12
    assert got["dsa_index_roofline"] == pytest.approx(
        100 * sparse_flops.index_flops_per_step(m, 1, 16384) / peak / 0.4)
    assert got["dsa_flash_roofline"] == pytest.approx(
        100 * sparse_flops.sparse_flash_flops_per_step(m, 1, 16384) / peak
        / 0.3)
    window = max(
        sparse_flops.window_flash_flops_per_step(m, 1, 16384) / peak,
        sparse_flops.flash_bytes_per_step(m, 16384, "swa_") / 819e9)
    assert got["mla_window_roofline"] == pytest.approx(100 * window / 0.03)
    assert got["dsa_select_share"] == pytest.approx(30.0)
    assert got["mla_proj_roofline.sparse"] == pytest.approx(
        100 * sparse_flops.proj_flops_per_step(m, 16384) / peak / 0.08)
    per_token = 6 * 16384 / peak
    assert got["mlp_roofline.sparse"] == pytest.approx(
        100 * per_token * 3 * 5120 * (13824 + 4 * 1536) / 0.25)
    assert got["head_loss_roofline.sparse"] == pytest.approx(
        100 * per_token * 5120 * 19008 / 0.05)
    assert got["unscoped_device_share.sparse"] == pytest.approx(20.0)
    assert got["sparse_mfu"] == pytest.approx(
        100 * sparse_flops.train_flops_per_step(m, 1, 16384, 16400.0)
        * 10 / 20.0 / peak)
    assert all(0 < v <= 100 for v in got.values())
    # the readers of the held share read this cell as they stand
    assert spec.metric_reader("moe_held_gmm_roofline")(obs) == pytest.approx(
        100 * 6 * 16500 * 3 * 5120 * 1536 / peak / 0.01)
    assert spec.metric_reader("moe_held_row_share")(obs) == pytest.approx(
        100 * 16400 / 524288)


def test_readers_find_nothing_in_another_models_run(traced_obs):
    """A program without the index's scopes (the parent's, or another
    cell's): every reader returns nothing and raises nothing."""
    other = spec.model_sizes(spec._read_json(os.path.join(
        spec.ROOT, "benchmark/configs/deepseek-v2-c1.json")))
    obs = traced_obs({"flash_kv_fwd": 0.1, "gmm": 0.1}, other,
                     scope_self_s={"mlp": 0.3, "head_loss": 0.1,
                                   "mla_q": 0.1},
                     moe_rows_routed=1, moe_rows_held=1.0)
    assert [spec.metric_reader(n)(obs) for n in NEW] == [None] * len(NEW)
    assert [spec.metric_reader(n)({}) for n in NEW] == [None] * len(NEW)
    # this model's trace without the scopes and the calls (a CPU rehearsal)
    obs = traced_obs({}, _model(), scope_self_s={"mlp": 0.3})
    for n in NEW[1:]:
        if n != "mlp_roofline.sparse":
            assert spec.metric_reader(n)(obs) is None, n
