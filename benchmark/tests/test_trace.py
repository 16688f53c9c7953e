"""The trace reduction against the two recorded traces under
benchmark/fixtures (see record_fixture.py there): a change to the
reduction that changes a number shows here."""

import os

import pytest

from benchmark.lib import spec, trace

FIX = os.path.join(spec.BENCH_DIR, "fixtures")


def test_interval_arithmetic():
    u = trace.union([(0, 2), (1, 3), (5, 6)])
    assert u == [(0, 3), (5, 6)] and trace.total(u) == 4
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert trace.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert trace.subtract([(0, 4)], []) == [(0, 4)]


def test_names():
    text = ('%while.42 = (s32[]{:T(128)}, bf16[4,1,256]{2,0,1:T(4,128)(2,1)S(1)})'
            ' while((s32[]) %tuple.1), condition=%c, body=%b')
    assert trace.op_short_name(text) == "while.42(while)"
    assert trace.op_kind('%ag = bf16[8]{0:T(8)} all-gather-start(bf16[2] %x)') \
        == "all-gather-start"
    assert trace.module_name("jit_step(123)") == "jit_step"


def test_serve_fixture():
    r = trace.reduce_trace(os.path.join(FIX, "serve.xplane.pb"))
    assert r["chips"] == 1
    # 2 prefill chunks, 3 decode chunks of 4 steps over 2 layers
    assert r["modules"]["jit_prefill_chunk"]["count"] == 2
    assert r["modules"]["jit_paged_decode_chunk"]["count"] == 3
    assert r["mosaic"]["jit_paged_decode_chunk"]["count"] == 3 * 4 * 2
    assert "jit_prefill_chunk" not in r["mosaic"]
    assert r["modules"]["jit_paged_decode_chunk"]["device_s"] == \
        pytest.approx(288.6e-6, rel=1e-3)
    assert r["mosaic"]["jit_paged_decode_chunk"]["device_s"] == \
        pytest.approx(104.371e-6, rel=1e-3)
    assert r["busy_s"] == pytest.approx(372.103e-6, rel=1e-3)
    assert r["window_s"] == pytest.approx(11.945034e-3, rel=1e-4)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["collective_s"] == 0
    # idle gaps carry the benchmark's own host spans
    assert {"bench.step", "bench.wait"} <= set(r["idle_gaps_s"])
    assert sum(r["idle_gaps_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=0.02)
    top = trace.breakdown(r)
    assert top["device_ops"][0][0] == \
        "jit_paged_decode_chunk:closed_call.9(custom-call)"
    assert len(top["device_ops"]) == 10
    # self times add up to the busy time (nothing counted twice)
    assert sum(r["op_self_s"].values()) == pytest.approx(r["busy_s"], rel=0.01)


def test_train_fixture():
    r = trace.reduce_trace(os.path.join(FIX, "train.xplane.pb"))
    assert r["modules"]["jit_step"]["count"] == 3
    # per step and layer: forward, recomputed forward, dq, dk/dv
    assert r["mosaic"]["jit_step"]["count"] == 3 * 2 * 4
    assert r["busy_s"] == pytest.approx(674.445e-6, rel=1e-3)
    assert r["idle_gaps_s"].keys() == {"bench.step"}


def test_metric_readers_on_the_fixture():
    r = trace.reduce_trace(os.path.join(FIX, "serve.xplane.pb"))
    obs = {"trace": r, "model": {"num_hidden_layers": 2}}
    ms = spec.metric_reader("decode_step_device_ms")(obs)
    assert ms == pytest.approx(1e3 * 288.6e-6 / 12, rel=1e-3)
    idle = spec.metric_reader("device_idle_share.serve")(obs)
    assert idle == pytest.approx(100 * (1 - 372.103 / 11945.034), rel=1e-3)
    # a reader that finds nothing to read returns nothing
    assert spec.metric_reader("decode_step_device_ms")({}) is None
    assert spec.metric_reader("prefix_hit_share")({}) is None
    assert spec.metric_reader("prefix_hit_share")(
        {"counters": {"prefix_hit_tokens": 3, "prefill_tokens_computed": 1}}
    ) == 75.0
