"""BENCHMARK.json against the files it points to."""

import os
import re

import pytest

from benchmark.lib import flops, peaks, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


CANDIDATES = "benchmark/candidates.json"


@pytest.mark.parametrize("file", ["BENCHMARK.json", CANDIDATES])
def test_every_name_has_its_file(file):
    b = spec.load_benchmark(file=file)
    for w in b["workloads"]:
        ctx = spec.resolve_cell(b, w["name"])
        tr = ctx["traffic"]
        assert hasattr(spec.generator(tr["kind"]), "generate")
        assert hasattr(spec.cell_runner(tr["family"]), "run")
        e2e = [m["name"] for m in ctx["end_to_end"]]
        assert "setup_s" in e2e and len(e2e) >= 2 and ctx["per_layer"]
        for m in ctx["per_layer"]:
            assert m["moves"] in e2e, (w["name"], m["name"])
        for m in ctx["end_to_end"] + ctx["per_layer"]:
            assert NAME.match(m["name"])
            assert callable(spec.metric_reader(m["name"]))
        assert len(w["why"]) <= 200


def test_the_contracts_limits():
    b = spec.load_benchmark()
    assert b["paths"] == ["benchmark"]
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}


@pytest.mark.parametrize("file", ["BENCHMARK.json", CANDIDATES])
def test_configs_state_what_the_program_is_given(file):
    b = spec.load_benchmark(file=file)
    pairs = {"hidden_size": "hidden_size", "intermediate_size":
             "intermediate_size", "num_hidden_layers": "num_layers",
             "num_attention_heads": "num_heads", "num_key_value_heads":
             "num_kv_heads", "vocab_size": "vocab_size", "head_dim":
             "head_dim", "rope_theta": "rope_theta", "rms_norm_eps":
             "rms_norm_eps", "tie_word_embeddings": "tie_embeddings",
             "attention_bias": "attn_qkv_bias"}
    for c in b["configs"]:
        cfg = spec._read_json(os.path.join(spec.ROOT, c["file"]))
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        for hf, ours in pairs.items():
            assert cfg[hf] == cfg["model_config"][ours], (c["name"], hf)
        assert cfg["model_config"]["param_dtype"] == cfg["torch_dtype"]


def test_sizes_from_shapes():
    cfgs = {c["name"]: spec.model_sizes(spec._read_json(
        os.path.join(spec.ROOT, c["file"])))
        for f in ("BENCHMARK.json", CANDIDATES)
        for c in spec.load_benchmark(file=f)["configs"]}
    assert abs(flops.total_params(cfgs["qwen2.5-3b"]) / 3.086e9 - 1) < 0.01
    assert abs(flops.total_params(cfgs["mistral-7b-v0.3-c4"]) / 3.76e9 - 1) \
        < 0.01
    assert abs(flops.total_params(cfgs["mistral-7b-v0.3-c1"]) / 1.14e9 - 1) \
        < 0.01
    assert flops.kv_bytes_per_token(cfgs["qwen2.5-3b"]) == 36864
    # a full Mistral-7B-v0.3 is 7.25 B parameters
    full = dict(cfgs["mistral-7b-v0.3-c1"], num_hidden_layers=32)
    assert abs(flops.total_params(full) / 7.248e9 - 1) < 0.01
    # 6N dominates at 4k context; attention adds 12*L*s*h*d/2... a few %
    m = cfgs["mistral-7b-v0.3-c1"]
    per_tok = flops.train_flops_per_token(m, 4096)
    assert 1.0 < per_tok / (6 * flops.matmul_params(m)) < 1.25
    assert flops.paged_attention_bytes(cfgs["qwen2.5-3b"], 65, 64, 2) == \
        2 * 2 * 64 * 36864
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    try:
        peaks.peaks("TPU v9")
    except KeyError as e:
        assert "no published peaks" in str(e)
    else:
        raise AssertionError("an unknown device must be an error")
