"""The trace reduction: synthetic device events with known answers, and
the reader on a real profiler trace recorded here on the CPU."""

import pytest

import bench_cells  # noqa: F401  (the repository root on the path)
from bench import tracing

MS = 1_000_000  # ns

SWEEP = ('%f.3 = f32[512,1]{1,0} custom-call(f32[512,1]{1,0} %a, f32[512,1]{1,0} %b, '
         'f32[512,1]{1,0} %c, f32[1,4096]{1,0} %d, f32[1,4096]{1,0} %e, f32[1,4096]{1,0} %g), '
         'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}')
KNN = ('%f.2 = f32[640,1]{1,0} custom-call(s32[5]{0} %n, f32[640,1]{1,0} %a, f32[640,1]{1,0} %b, '
       'f32[5,1,1024]{2,1,0} %c, f32[5,1,1024]{2,1,0} %d), custom_call_target="tpu_custom_call"')
OTHER_KERNEL = ('%f.9 = f32[8,1]{1,0} custom-call(f32[8,1]{1,0} %a), '
                'custom_call_target="tpu_custom_call"')
WHILE = "%while.4 = (s32[]) while((s32[]) %t), condition=%c, body=%b"
FUSION = "%fusion.7 = f32[64]{0} fusion(f32[64]{0} %p), kind=kLoop, calls=%fc"


def synthetic():
    dev = "/device:TPU:0"
    ops = [
        (dev, KNN, 0 * MS, 10 * MS),          # starts before the window: clipped
        (dev, WHILE, 12 * MS, 40 * MS),       # holds two fusions
        (dev, FUSION, 14 * MS, 20 * MS),
        (dev, FUSION, 25 * MS, 30 * MS),
        (dev, SWEEP, 50 * MS, 90 * MS),
        (dev, OTHER_KERNEL, 90 * MS, 92 * MS),
        (dev, FUSION, 120 * MS, 130 * MS),    # ends after the window: clipped
    ]
    spans = [("bench.window", 5 * MS, 125 * MS), ("bench.call", 5 * MS, 60 * MS),
             ("bench.call", 60 * MS, 125 * MS)]
    return ops, spans


def test_reduce_known_answers():
    ops, spans = synthetic()
    r = tracing.reduce(ops, spans, tracing.kernel_groups())
    assert r["window_s"] == pytest.approx(0.120)
    # union: [5,10] + [12,40] + [50,92] + [120,125]
    assert r["busy_s"] == pytest.approx((5 + 28 + 42 + 5) / 1e3)
    assert r["kernel_s"]["phase1_knn"] == pytest.approx(0.005)
    assert r["kernel_s"]["phase2_sweep"] == pytest.approx(0.040)
    assert r["pallas_s"] == pytest.approx(0.005 + 0.040 + 0.002)
    # the while's self time (28 - 6 - 5) plus its fusions and the clipped one
    assert r["xla_s"] == pytest.approx((17 + 6 + 5 + 5) / 1e3)
    assert r["pallas_s"] + r["xla_s"] == pytest.approx(r["busy_s"])
    names = [n for n, _ in r["breakdown"]["device_ops"]]
    assert names[0] == "phase2_sweep" and "%while.4 while" in names and "%f.9 pallas" in names
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["bench.call", pytest.approx(0.028)]       # [92, 120]
    assert [g for _, g in gaps] == sorted((g for _, g in gaps), reverse=True)
    assert sum(g for _, g in gaps) == pytest.approx(r["window_s"] - r["busy_s"])


def test_signatures_bind_sizes():
    ops = tracing.operands(SWEEP)
    assert tracing.signature_matches(ops, ["f32[n,1]"] * 3 + ["f32[1,m]"] * 3)
    assert not tracing.signature_matches(ops, ["f32[n,1]"] * 3 + ["f32[1,n]"] * 3)
    assert not tracing.signature_matches(ops, ["f32[n,1]"] * 2 + ["f32[1,m]"] * 3)
    assert tracing.group_of(SWEEP.replace("tpu_custom_call", "other"), tracing.kernel_groups()) is None


def test_kernel_names_win_where_the_trace_shows_them():
    named = OTHER_KERNEL.replace("%f.9", "%_weight_kernel_soa.1")
    assert tracing.group_of(named, tracing.kernel_groups()) == "phase2_sweep"


def test_no_window_span_is_an_error():
    ops, spans = synthetic()
    with pytest.raises(ValueError):
        tracing.reduce(ops, [s for s in spans if s[0] != "bench.window"], tracing.kernel_groups())


def test_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones((128,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    ops, spans = tracing.load(tracing.find_xplane(str(tmp_path)))
    assert [s[0] for s in spans].count("bench.call") == 3
    window = next(s for s in spans if s[0] == "bench.window")
    assert all(window[1] <= s[1] and s[2] <= window[2] for s in spans)
    r = tracing.reduce(ops, spans, tracing.kernel_groups())
    assert r["window_s"] > 0 and r["busy_s"] >= 0
