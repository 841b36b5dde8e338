"""The split of a traced window by the program's own names
(``bench/scopes.py``): synthetic events with known answers, a compiled
program's text, and one small traced run on the CPU."""

import pytest

import bench_cells
from bench import scopes, tracing

MS = 1_000_000  # ns
DEV = "/device:TPU:0"


def _path(scope):
    return "jit(_execute_with_stats_jit)" + "".join(f"/{s}" for s in scope) + "/op"


# the compiled program's op_name paths, by (module, instruction)
OP_NAMES = {("jit__execute_with_stats_jit", op): _path(scope) for op, scope in [
    ("fusion.1", ["aidw.sort"]), ("while.2", ["aidw.gather"]), ("fusion.3", ["aidw.gather"]),
    ("fusion.4", ["aidw.phase2", "aidw.phase2.farfield"]),
    ("_weight_kernel_soa.1", ["aidw.phase2", "_weight_kernel_soa"]),
    ("fusion.6", ["aidw.stats"])]}


def _op(name):
    """An operation's event name on a TPU trace: its HLO text, no metadata."""
    return f"%{name} = f32[8]{{0}} fusion(%p), kind=kLoop"


def synthetic():
    ops = [
        (DEV, _op("fusion.1"), 0, 10 * MS, {}),                    # clipped
        (DEV, _op("while.2"), 12 * MS, 40 * MS, {}),               # holds two
        (DEV, _op("fusion.3"), 14 * MS, 20 * MS, {}),
        (DEV, _op("fusion.4"), 25 * MS, 30 * MS, {}),
        (DEV, "%_weight_kernel_soa.1 = f32[8,1]{1,0} custom-call(%a), "
              'custom_call_target="tpu_custom_call"', 50 * MS, 90 * MS, {}),
        (DEV, _op("fusion.5"), 90 * MS, 92 * MS, {}),               # no op_name
        (DEV, _op("fusion.6"), 120 * MS, 130 * MS, {}),            # clipped
    ]
    spans = [("bench.window", 5 * MS, 125 * MS, {}),
             ("bench.call", 5 * MS, 60 * MS, {}), ("bench.call", 60 * MS, 125 * MS, {}),
             ("aidw.serving.execute", 6 * MS, 59 * MS, {"call": 0}),
             ("aidw.serving.sync", 10 * MS, 58 * MS, {}),
             ("aidw.serving.execute", 61 * MS, 100 * MS, {"call": 1}),
             ("aidw.serving.sync", 62 * MS, 96 * MS, {})]
    return ops, spans


def test_scope_s_sums_to_the_device_self_time():
    ops, spans = synthetic()
    r = scopes.reduce(ops, spans, OP_NAMES)
    s = r["scope_s"]
    assert s["aidw.sort"] == pytest.approx(0.005)
    # the while's self time (28 - 6 - 5) and its gather fusion
    assert s["aidw.gather"] == pytest.approx(0.017 + 0.006)
    assert s["aidw.phase2.farfield"] == pytest.approx(0.005)
    assert s["aidw.phase2"] == pytest.approx(0.040)
    assert s["aidw.stats"] == pytest.approx(0.005)
    assert s["unscoped"] == pytest.approx(0.002)
    old = tracing.reduce([op[:4] for op in ops], [sp[:3] for sp in spans], tracing.kernel_groups())
    assert sum(s.values()) == pytest.approx(old["xla_s"] + old["pallas_s"], rel=1e-9)
    top = r["device_ops"][0]
    assert top[:2] == ["%_weight_kernel_soa.1 pallas", "aidw.phase2"]
    assert top[2] == pytest.approx(0.040)


def test_an_event_stat_names_the_instruction():
    """CPU traces name the module and instruction in event stats."""
    ops, spans = synthetic()
    stated = [(d, "ignored", a, b, {"hlo_module": "jit__execute_with_stats_jit",
                                    "hlo_op": n[1:].split(" ")[0]}) for d, n, a, b, _st in ops]
    assert (scopes.reduce(stated, spans, OP_NAMES)["scope_s"]
            == pytest.approx(scopes.reduce(ops, spans, OP_NAMES)["scope_s"]))
    # without the compiled program's text every operation is unscoped
    s = scopes.reduce(ops, spans)["scope_s"]
    assert list(s) == ["unscoped"]


def test_idle_gaps_take_the_innermost_program_span():
    ops, spans = synthetic()
    gaps = scopes.reduce(ops, spans, OP_NAMES)["idle_gaps"]
    # [92, 120] lies in call 1's bench.call only; [40, 50] in call 0's sync
    assert gaps[0] == ["bench.call", pytest.approx(0.028)]
    assert ["aidw.serving.sync", pytest.approx(0.010)] in gaps
    assert [n for n, _ in gaps].count("aidw.serving.sync") == 2   # [10, 12] too


def test_spans_and_host_time_per_call():
    ops, spans = synthetic()
    r = scopes.reduce(ops, spans)
    assert r["span_s"]["aidw.serving.execute"] == [2, pytest.approx(0.053 + 0.039)]
    assert r["span_s"]["bench.call"][0] == 2 and "bench.window" not in r["span_s"]
    # (53 - 48) and (39 - 34) ms outside the sync
    assert r["host_ms_per_call"] == pytest.approx(5.0)


def test_op_names_of_a_compiled_program():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("aidw.sort"):
            y = jnp.sort(x)
        with jax.named_scope("aidw.phase2"):
            return jnp.cumsum(y) * 2.0

    names = scopes.op_names_of(f.lower(jnp.ones(64)).compile().as_text())
    found = {scopes.innermost(p) for p in names.values()}
    assert {"aidw.sort", "aidw.phase2"} <= found
    assert all(module is not None for module, _op in names)


def test_small_traced_run_reads_the_program():
    import jax

    cell = bench_cells.small_cell("paper-uniform-1000k.scatter")
    out = scopes.run(cell, 2147483659, 0.0, jax.devices()[0])
    assert out["result"]["correct"]
    calls = out["result"]["attempted"] // cell["traffic"]["queries"]["batch"]
    assert out["scopes"]["span_s"]["aidw.serving.execute"][0] == calls
    assert out["scopes"]["host_ms_per_call"] > 0.0
    d = out["derived"]
    assert d["engine.compiles_in_window"] == 0
    assert d["engine.setup_compile_s"] > 0.0
    assert d["serving.warm_replan_s"] >= 0.0
    assert out["program"]["setup"]["compiles"] > 0


def test_recorder_passes_the_server_through():
    class Server:
        build_s = 1.5

        def call(self, qx, qy):
            return qx, qy, None

        def counters(self):
            return {"plan_build_s": self.build_s}

    rec = scopes._Recorder(Server())
    assert rec.build_s == 1.5 and rec.call(1, 2) == (1, 2, None) and rec.batch == (1, 2)
    assert rec.counters() == {"plan_build_s": 1.5} and len(rec.snapshots) == 1


def test_program_spans_leave_the_harness_reading_unchanged(tmp_path):
    """``bench/tracing.py`` reads ``bench.*`` spans only, so the program's
    own spans change no key of its result; this module reads both."""
    import jax
    import jax.numpy as jnp

    from repro import telemetry

    f = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones((128,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.call"), telemetry.span("serving.execute", call=i):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = tracing.find_xplane(str(tmp_path))
    _ops, spans = tracing.load(path)
    assert {s[0] for s in spans} == {"bench.window", "bench.call"}
    _ops, both = scopes.load(path)
    calls = [s for s in both if s[0] == "aidw.serving.execute"]
    assert [int(s[3]["call"]) for s in calls] == [0, 1, 2]
