"""lib/scopes.py, lib/program_spans.py and the readers built on them,
against the recorded trace ``fixtures/train_scoped.xplane.pb`` (see
``fixtures/record_scoped_fixture.py``) and a written-out span file. A
change to the reduction, or to the model's scopes after a re-recording,
shows here."""

import json
import os
import shutil

import pytest

from benchmark.lib import (flops, program_spans, scope_flops, scopes, spec,
                           trace)

FIX = os.path.join(spec.BENCH_DIR, "fixtures", "train_scoped.xplane.pb")
TRAIN_SCOPES = {"embed", "attn_qkv", "flash", "attn_out", "mlp", "head_loss"}


def test_scope_of_a_path():
    assert scopes.scope_of(
        "jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
        "rematted_computation/attn_qkv/dot_general:") == "attn_qkv"
    assert scopes.scope_of("jit(step)/transpose(jvp(head_loss))/mul:") == \
        "head_loss"
    assert scopes.scope_of(
        "jit(step)/jvp()/while/body/closed_call/flash/flash_fwd/pallas_call:"
    ) == "flash"
    # the innermost scope; a fusion is named by its first part
    assert scopes.scope_of("jit(f)/mlp/attn/dot_general:") == "attn"
    assert scopes.scope_of("jit(f)/jvp(mlp)/add:;jit(f)/jvp(embed)/mul:") == \
        "mlp"
    for path in ("", "jit(step)/add:", "jit(step)/jvp()/while/body/squeeze:",
                 "jit(step)/mlp_norm/mul:", "jit(sample_tokens)/argmax:"):
        assert scopes.scope_of(path) == "unscoped", path


def test_the_wire_reader_agrees_with_jaxs():
    """Same events, same clock as ``jax.profiler.ProfileData`` gives
    ``lib/trace.py`` (which rounds to whole ns)."""
    r = scopes.reduce_scopes(FIX)
    t = trace.reduce_trace(FIX)
    assert r["chips"] == t["chips"] == 1
    assert r["busy_s"] == pytest.approx(t["busy_s"], rel=2e-3)
    planes = scopes.read_planes(FIX)
    dev = planes["/device:TPU:0"]
    assert {ln["name"] for ln in dev["lines"]} >= {"XLA Modules", "XLA Ops"}
    ops = next(ln for ln in dev["lines"] if ln["name"] == "XLA Ops")
    assert len(ops["events"]) == 1131
    mods = next(ln for ln in dev["lines"] if ln["name"] == "XLA Modules")
    assert [trace.module_name(dev["names"][m]) for m, _, _ in
            mods["events"]] == ["jit_step"] * 3


def test_scoped_fixture():
    r = scopes.reduce_scopes(FIX)
    by = r["scope_self_s"]
    assert set(by) == TRAIN_SCOPES | {"unscoped"}
    # scoped + unscoped is the device's busy time (nothing twice, nothing
    # lost), and both agree with lib/trace.py's total
    assert sum(by.values()) == pytest.approx(r["busy_s"], rel=0.01)
    t = trace.reduce_trace(FIX)
    assert sum(by.values()) == pytest.approx(
        sum(t["op_self_s"].values()), rel=0.01)
    assert r["busy_s"] == pytest.approx(674.818e-6, rel=1e-3)
    assert by["mlp"] == pytest.approx(120.397e-6, rel=1e-3)
    assert by["flash"] == pytest.approx(165.373e-6, rel=1e-3)
    assert by["unscoped"] == pytest.approx(150.675e-6, rel=1e-3)
    # the Mosaic calls carry their kernels' names; together they are
    # what lib/trace.py finds by the custom-call target
    assert set(r["kernel_s"]) == {"flash_fwd", "flash_bwd_dq",
                                  "flash_bwd_dkv"}
    assert sum(r["kernel_s"].values()) == pytest.approx(
        t["mosaic"]["jit_step"]["device_s"], rel=2e-3)
    # the kernels are the flash scope's time, but for the reshapes there
    assert sum(r["kernel_s"].values()) <= by["flash"]
    # host plane: the program's span, on the device's clock. The two
    # gaps between the three steps both have their middle inside it (the
    # span ends when block_until_ready has returned, after the device has
    # gone idle), so no idle time is unnamed here
    assert r["program_spans"] == ["rtpu.fixture.step"]
    assert r["idle_unnamed_s"] == 0.0
    assert r["idle_s"] == pytest.approx(
        t["window_s"] - t["busy_s"], rel=0.02)


def test_idle_gaps_without_a_program_span(monkeypatch):
    """Against lib/trace.py's own naming of gaps, on PR 23's serving
    trace, whose host spans are the benchmark's (``bench.*``)."""
    monkeypatch.setattr(scopes, "PROGRAM_SPAN_PREFIX", "bench.")
    r = scopes.reduce_scopes(os.path.join(spec.BENCH_DIR, "fixtures",
                                          "serve.xplane.pb"))
    t = trace.reduce_trace(os.path.join(spec.BENCH_DIR, "fixtures",
                                        "serve.xplane.pb"))
    assert r["program_spans"] == ["bench.step", "bench.wait"]
    assert r["idle_unnamed_s"] == pytest.approx(
        t["idle_gaps_s"]["_no_span_"], rel=1e-3)
    assert r["idle_s"] == pytest.approx(sum(t["idle_gaps_s"].values()),
                                        rel=1e-3)


def _obs(cell, **more):
    return {"cell": {"name": cell}, "trace": {"busy_s": 1.0}, **more}


@pytest.fixture
def traced_cell():
    """A cell's trace directory as the cell runners leave it."""
    name = "test-scopes-cell"
    d = os.path.join(spec.ROOT, ".bench_tmp", "trace-" + name)
    shutil.rmtree(d, ignore_errors=True)
    sub = os.path.join(d, "plugins", "profile", "2026_01_01")
    os.makedirs(sub)
    shutil.copy(FIX, os.path.join(sub, "host.xplane.pb"))
    try:
        yield name
    finally:
        shutil.rmtree(d, ignore_errors=True)


MODEL = {"hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 2,
         "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 128,
         "vocab_size": 512}


def test_scope_readers_on_the_fixture(traced_cell):
    obs = _obs(traced_cell, model=MODEL, device={"device_kind": "TPU v5 lite"},
               train={"traced_steps": 3, "tokens_per_step": 1024, "chips": 1})
    r = scopes.for_obs(obs)
    assert os.path.exists(os.path.join(scopes.trace_dir_of(obs),
                                       "scopes.json"))        # cached
    assert scopes.for_obs(obs) == r
    peak = 197e12
    mlp = spec.metric_reader("mlp_roofline")(obs)
    assert mlp == pytest.approx(
        100 * (18 * 256 * 512 * 2 * 1024 / peak)
        / (r["scope_self_s"]["mlp"] / 3), rel=1e-6)
    proj = spec.metric_reader("attn_proj_roofline")(obs)
    need = 6 * 2 * (256 * 256 * 2 + 2 * 256 * 128) * 1024
    assert proj == pytest.approx(100 * (need / peak) / ((
        r["scope_self_s"]["attn_qkv"] + r["scope_self_s"]["attn_out"]) / 3),
        rel=1e-6)
    head = spec.metric_reader("head_loss_roofline")(obs)
    assert head == pytest.approx(
        100 * (6 * 256 * 512 * 1024 / peak)
        / (r["scope_self_s"]["head_loss"] / 3), rel=1e-6)
    for v in (mlp, proj, head):     # a toy size: far under the roofline
        assert 0 < v < 100
    share = spec.metric_reader("unscoped_device_share")(obs)
    assert share == pytest.approx(100 * 150.675 / 674.818, rel=1e-3)
    assert spec.metric_reader("idle_unnamed_share.serve")(obs) == 0.0


def test_scope_readers_find_nothing_without_scopes_or_a_trace(tmp_path):
    """A program that names no scope (the parent of PR 24), or an
    untraced run: every reader returns nothing and none raises."""
    names = ("mlp_roofline", "attn_proj_roofline", "head_loss_roofline",
             "unscoped_device_share", "idle_unnamed_share.serve")
    for obs in ({}, {"cell": {"name": "no-such-cell"}},
                _obs("no-such-cell"), {"trace": {"busy_s": 1.0}}):
        for n in names:
            assert spec.metric_reader(n)(obs) is None, (n, obs)
    # the trace PR 23 recorded: no model scope, no program span
    name = "test-unscoped-cell"
    d = os.path.join(spec.ROOT, ".bench_tmp", "trace-" + name)
    sub = os.path.join(d, "plugins", "profile", "x")
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(sub)
    try:
        shutil.copy(os.path.join(spec.BENCH_DIR, "fixtures",
                                 "train.xplane.pb"), sub)
        obs = _obs(name, model=MODEL, device={"device_kind": "TPU v5 lite"},
                   train={"traced_steps": 3, "tokens_per_step": 1024,
                          "chips": 1})
        assert set(scopes.for_obs(obs)["scope_self_s"]) == {"unscoped"}
        for n in names:
            assert spec.metric_reader(n)(obs) is None, n
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_scope_flops_split_the_steps_total():
    for file in ("mistral-7b-v0.3-c1.json", "mistral-7b-v0.3-c4.json",
                 "qwen2.5-3b.json"):
        m = spec.model_sizes(spec._read_json(os.path.join(
            spec.BENCH_DIR, "configs", file)))
        parts = sum(fn(m) for fn in scope_flops.PARAMS.values())
        assert parts == flops.matmul_params(m)
        seq = 4096
        split = sum(scope_flops.train_flops(m, p, 1.0)
                    for p in scope_flops.PARAMS)
        attn = 3.0 * m["num_hidden_layers"] * flops.causal_attention_flops_fwd(
            1, seq, m["num_attention_heads"], m["head_dim"]) / seq
        assert split + attn == pytest.approx(
            flops.train_flops_per_token(m, seq), rel=1e-12)
    c1 = spec.model_sizes(spec._read_json(os.path.join(
        spec.BENCH_DIR, "configs", "mistral-7b-v0.3-c1.json")))
    assert scope_flops.train_flops(c1, "mlp", 8192) == \
        18 * 4096 * 14336 * 8192 * 4
    assert scope_flops.train_flops(c1, "head_loss", 8192) == \
        6 * 4096 * 32768 * 8192


def test_program_span_readers(tmp_path):
    name = "test-spans-cell"
    d = os.path.join(spec.ROOT, ".bench_tmp", "train-" + name)
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(os.path.join(d, name))
    obs = {"cell": {"name": name}}
    readers = {n: spec.metric_reader(n) for n in (
        "setup_runtime_s", "setup_gang_s", "setup_chip_open_s")}
    try:
        for read in readers.values():          # no file: nothing, no error
            assert read(obs) is None and read({}) is None

        def ev(name, t0, dur, pid=1):
            return {"name": name, "cat": "span", "ph": "X", "ts": t0 * 1e6,
                    "dur": dur * 1e6, "pid": pid, "tid": 1, "args": {}}

        with open(program_spans.spans_file(obs), "w") as f:
            json.dump([ev("rtpu.init", 100.0, 0.25),
                       ev("rtpu.train.start", 101.0, 12.0),
                       ev("rtpu.train.place", 101.0, 1.0),
                       ev("rtpu.backend.on_start", 102.0, 10.5),
                       ev("rtpu.backend.devices", 103.0, 7.0, pid=2),
                       ev("rtpu.backend.devices", 103.5, 8.5, pid=3),
                       # a second gang after a failure is not the set-up
                       ev("rtpu.train.start", 200.0, 5.0),
                       ev("rtpu.backend.devices", 201.0, 30.0, pid=4)], f)
        assert readers["setup_runtime_s"](obs) == pytest.approx(0.25)
        assert readers["setup_chip_open_s"](obs) == pytest.approx(8.5)
        assert readers["setup_gang_s"](obs) == pytest.approx(12.0 - 8.5)
        # a CPU gang opens no chip
        with open(program_spans.spans_file(obs), "w") as f:
            json.dump([ev("rtpu.init", 100.0, 0.25),
                       ev("rtpu.train.start", 101.0, 3.0)], f)
        assert readers["setup_chip_open_s"](obs) is None
        assert readers["setup_gang_s"](obs) == pytest.approx(3.0)
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_counter_ratio_readers():
    c = {"inflight_depth_sum": 900, "ticks": 100, "slot_ticks_drained": 30,
         "slot_ticks_occupied": 120, "admit_ns": 4_000_000,
         "dispatch_ns": 6_000_000, "reap_wait_ns": 50_000_000, "steps": 10}
    obs = {"counters": c}
    assert spec.metric_reader("inflight_depth_mean")(obs) == 9.0
    assert spec.metric_reader("drained_slot_share")(obs) == 25.0
    assert spec.metric_reader("tick_host_ms_per_step")(obs) == \
        pytest.approx(1.0)
    assert spec.metric_reader("reap_wait_ms_per_step")(obs) == \
        pytest.approx(5.0)
    for n in ("inflight_depth_mean", "drained_slot_share",
              "tick_host_ms_per_step", "reap_wait_ms_per_step"):
        assert spec.metric_reader(n)({}) is None


NEW = {"BENCHMARK.json": [
    "setup_runtime_s", "setup_gang_s", "setup_chip_open_s", "mlp_roofline",
    "attn_proj_roofline", "head_loss_roofline", "unscoped_device_share"],
    "benchmark/candidates.tracing.json": [
    "inflight_depth_mean", "drained_slot_share", "tick_host_ms_per_step",
    "reap_wait_ms_per_step", "idle_unnamed_share.serve"]}


@pytest.mark.parametrize("file", sorted(NEW))
def test_every_new_entry_has_its_file_and_sits_at_the_end(file):
    if file == "BENCHMARK.json":
        b = spec.load_benchmark(file=file)
    else:   # the serving entries wait in a file of their own, merged for a run
        from benchmark.tests import run_candidate_tracing
        b = run_candidate_tracing.merged()
        assert spec.load_benchmark(file=file)["per_layer"] == \
            b["per_layer"][-len(NEW[file]):]
    names = [m["name"] for m in b["per_layer"]]
    assert names[-len(NEW[file]):] == NEW[file]
    cells = [w["name"] for w in b["workloads"]]
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"][-len(NEW[file]):]:
        assert callable(spec.metric_reader(m["name"]))
        assert m["workloads"] == cells and m["moves"] in e2e
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("program_span", "program_counter",
                               "device_trace")
        assert ("roofline" in m["name"]) == (m["unit"] == "%"
                                             and m["better"] == "higher")
