"""eg_devprof tier-1 pins: exact recompile arithmetic under injected
shape drift, kill-switch silence, the serve compile-storm guard
(counter + strict raise, on a live micro-batched drill), transfer-byte
counters, device-memory gauges, the merged host+device trace export,
and the metrics_text families.

Counter discipline: ``device_compiles`` is GLOBAL (auxiliary compiles
— a stray jnp.ones — bump it too), so tests pin the per-watched-
function ``device_recompiles`` arithmetic exactly and only assert
monotonicity for the global count."""

import numpy as np
import pytest

from euler_tpu import devprof
from euler_tpu.graph import native


@pytest.fixture(autouse=True)
def _clean_slate():
    from euler_tpu.telemetry import set_telemetry, telemetry_reset

    devprof.install()
    native.reset_counters()
    telemetry_reset()
    devprof.devprof_reset()
    set_telemetry(True)
    devprof.set_devprof(True)
    yield
    # leave the worker as it was found: the compile listener out and no
    # sampler ticking, or the file that runs next in this worker records
    # ``compile`` spans and sampler ticks it never asked for
    devprof.uninstall()
    native.reset_counters()
    telemetry_reset()
    devprof.devprof_reset()
    set_telemetry(True)
    devprof.set_devprof(True)


def _counters():
    return native.counters()


# ------------------------------------------------------- recompile ledger


def test_recompile_exact_arithmetic_under_shape_drift():
    import jax
    import jax.numpy as jnp

    step = devprof.watch(
        jax.jit(lambda x: (x * 2.0).sum()), name="drift_step"
    )
    x = jnp.ones((8, 2), jnp.float32)
    step(x).block_until_ready()  # warmup compile: NOT a recompile
    step(x).block_until_ready()  # in-bucket: no compile at all
    assert _counters()["device_recompiles"] == 0
    assert devprof.recompile_ledger() == []

    # injected drift: off-bucket batch -> exactly ONE journaled recompile
    step(jnp.ones((5, 2), jnp.float32)).block_until_ready()
    assert _counters()["device_recompiles"] == 1
    led = devprof.recompile_ledger()
    assert len(led) == 1
    assert led[0]["fn"] == "drift_step"
    assert led[0]["diff"] == ["leaf0: (8, 2) float32 -> (5, 2) float32"]

    # the drifted shape is now cached: repeating it compiles nothing
    step(jnp.ones((5, 2), jnp.float32)).block_until_ready()
    assert _counters()["device_recompiles"] == 1
    assert len(devprof.recompile_ledger()) == 1


def test_dtype_drift_is_attributed():
    import jax
    import jax.numpy as jnp

    step = devprof.watch(jax.jit(lambda x: x.sum()), name="dtype_step")
    step(jnp.ones((4,), jnp.float32)).block_until_ready()
    step(jnp.ones((4,), jnp.int32)).block_until_ready()
    led = devprof.recompile_ledger()
    assert len(led) == 1
    assert led[0]["diff"] == ["leaf0: (4,) float32 -> (4,) int32"]


def test_compile_counters_and_histogram_flow():
    import jax
    import jax.numpy as jnp

    from euler_tpu import telemetry as T

    before = _counters()["device_compiles"]
    f = devprof.watch(jax.jit(lambda x: x + 1), name="hist_step")
    f(jnp.ones((3, 3))).block_until_ready()
    data = T.telemetry_json()
    assert _counters()["device_compiles"] > before
    h = data["hist"].get("phase:compile")
    assert h is not None and h["count"] >= 1
    s = devprof.compile_summary(data)
    assert s["compile_events"] >= 1 and s["compile_ms_total"] > 0


def test_strict_raises_after_computing():
    import jax
    import jax.numpy as jnp

    step = devprof.watch(
        jax.jit(lambda x: x.sum()), name="strict_step", strict=True
    )
    step(jnp.ones((6,))).block_until_ready()
    with pytest.raises(devprof.RecompileError, match="strict_step"):
        step(jnp.ones((7,)))
    # the breach was still counted + journaled before the raise
    assert _counters()["device_recompiles"] == 1
    assert devprof.recompile_ledger()[0]["fn"] == "strict_step"


def test_mark_warm_declares_out_of_band_warmup():
    import jax
    import jax.numpy as jnp

    step = devprof.watch(jax.jit(lambda x: x * x), name="warm_step")
    step.mark_warm()
    # first tracked call compiles, but warmup was declared done -> it
    # journals as a recompile (the serve out-of-band warmup contract)
    step(jnp.ones((2, 2))).block_until_ready()
    assert _counters()["device_recompiles"] == 1


# ----------------------------------------------------------- kill-switch


def test_killswitch_writes_nothing():
    import jax
    import jax.numpy as jnp

    devprof.set_devprof(False)
    step = devprof.watch(jax.jit(lambda x: x - 1), name="off_step")
    step(jnp.ones((4,))).block_until_ready()
    step(jnp.ones((9,))).block_until_ready()  # would be a recompile
    c = _counters()
    assert c["device_compiles"] == 0
    assert c["device_recompiles"] == 0
    assert devprof.recompile_ledger() == []
    assert devprof.count_h2d(jnp.ones((16,))) == 0
    assert devprof.count_d2h(jnp.ones((16,))) == 0
    assert c["h2d_bytes"] == 0 and c["d2h_bytes"] == 0
    assert devprof.sample_device_mem() == (0, 0)


# ------------------------------------------------- transfers and memory


def test_transfer_byte_arithmetic():
    import jax.numpy as jnp

    batch = {"a": jnp.ones((8, 4), jnp.float32),
             "b": jnp.ones((8,), jnp.int32)}
    n = devprof.count_h2d(batch)
    assert n == 8 * 4 * 4 + 8 * 4
    assert _counters()["h2d_bytes"] == n
    m = devprof.count_d2h(batch["a"])
    assert m == 8 * 4 * 4
    assert _counters()["d2h_bytes"] == m


def test_device_mem_gauges_reach_resource_section():
    import jax.numpy as jnp

    from euler_tpu import telemetry as T

    keep = jnp.ones((128, 64), jnp.float32)  # held ref -> census sees it
    nbytes, buffers = devprof.sample_device_mem()
    assert nbytes >= keep.nbytes and buffers >= 1
    res = T.telemetry_json()["resource"]
    assert res["device_mem_bytes"] == nbytes
    assert res["device_mem_peak_bytes"] >= nbytes
    assert res["device_buffers"] == buffers
    # peak is monotone: a smaller re-sample must not lower it
    native.lib().eg_devprof_set_mem(1, 1)
    res2 = T.telemetry_json()["resource"]
    assert res2["device_mem_bytes"] == 1
    assert res2["device_mem_peak_bytes"] >= nbytes
    # telemetry_reset clears the gauges (fresh run = fresh high-water)
    T.telemetry_reset()
    res3 = T.telemetry_json()["resource"]
    assert res3["device_mem_peak_bytes"] == 0


def test_feature_table_gauges_and_route_log(fixture_dir, caplog):
    """build_consts says once what width the feature table is stored at
    (the route-log line beside 'draw path: ...') and sets the two width
    gauges, which outlive a reset of the measurements and reach
    metrics_text()."""
    import logging

    import euler_tpu
    from euler_tpu import telemetry as T
    from euler_tpu.models import SupervisedGraphSage

    model = SupervisedGraphSage(
        label_idx=2, label_dim=3, metapath=[[0, 1]], fanouts=[2], dim=8,
        feature_idx=0, feature_dim=50, max_id=16, device_features=True,
    )
    g = euler_tpu.Graph(directory=fixture_dir)
    try:
        with caplog.at_level(logging.INFO, logger="euler_tpu"):
            model.build_consts(g)
    finally:
        g.close()
    assert (
        "feature table: [18, 50] float32 stored [18, 128], rows contiguous"
        in caplog.text
    )
    T.telemetry_reset()
    res = T.telemetry_json()["resource"]
    assert res["feature_table_width"] == 50
    assert res["feature_table_stored_width"] == 128
    text = euler_tpu.metrics_text()
    assert "eg_feature_table_width 50" in text
    assert "eg_feature_table_stored_width 128" in text
    # the kill-switch silences the device plane, this gauge included
    devprof.set_devprof(False)
    devprof.record_feature_table(7, 128)
    assert T.telemetry_json()["resource"]["feature_table_width"] == 50


def test_store_table_gauges_and_route_log(fixture_dir, caplog):
    """train() says once what the per-node stores of the state are and
    how the device keeps them (one route-log line) and sets the two
    width gauges, which outlive a reset of the measurements and reach
    metrics_text(). A model without stores says nothing."""
    import logging

    import jax

    import euler_tpu
    from euler_tpu import telemetry as T
    from euler_tpu import train as train_lib
    from euler_tpu.models import ScalableSage, SupervisedGraphSage

    model = ScalableSage(
        label_idx=2, label_dim=3, edge_type=[0, 1], fanout=2, num_layers=2,
        dim=8, max_id=16, concat=True, feature_idx=0, feature_dim=2,
        device_features=True,
    )
    g = euler_tpu.Graph(directory=fixture_dir)
    try:
        with caplog.at_level(logging.INFO, logger="euler_tpu"):
            train_lib.train(model, g, lambda s: g.sample_node(8, -1),
                            num_steps=1, log_every=1)
        first = caplog.text
        caplog.clear()
        with caplog.at_level(logging.INFO, logger="euler_tpu"):
            SupervisedGraphSage(
                label_idx=2, label_dim=3, metapath=[[0, 1]], fanouts=[2],
                dim=8, feature_idx=0, feature_dim=2, max_id=16,
            ).describe_state({"params": {}})
    finally:
        g.close()
    assert "store table:" not in caplog.text
    # the CPU keeps a [18, 8] table row-major and untiled: 8 lanes a row
    assert ("store table: [18, 8] float32 x 2 (stores and gradient "
            "stores), device layout major_to_minor=(0, 1) tiling=(): rows "
            "contiguous, stored 8 wide") in first
    assert first.count("store table:") == 1
    T.telemetry_reset()
    res = T.telemetry_json()["resource"]
    assert res["store_table_width"] == 8
    assert res["store_table_stored_width"] == 8
    text = euler_tpu.metrics_text()
    assert "eg_store_table_width 8" in text
    assert "eg_store_table_stored_width 8" in text
    # a column-major table reads 0: its rows are not contiguous
    devprof.record_store_table(64, 0)
    assert T.telemetry_json()["resource"]["store_table_stored_width"] == 0
    assert jax.devices()[0].platform == "cpu"


# ------------------------------------------------------ serve guard drill


def _sage():
    from euler_tpu.models import SupervisedGraphSage

    return SupervisedGraphSage(
        label_idx=2, label_dim=3, metapath=[[0, 1], [0, 1]],
        fanouts=[3, 2], dim=8, feature_idx=0, feature_dim=2, max_id=16,
    )


def _server(graph, **kw):
    import jax

    from euler_tpu.serve import EmbedServer
    from euler_tpu.train import get_optimizer

    model = _sage()
    state = model.init_state(
        jax.random.PRNGKey(3), graph, np.arange(8),
        get_optimizer("adam", 0.01),
    )
    return EmbedServer(
        model, graph, state, max_batch=8, max_wait_us=2000,
        queue_cap=16, slo_ms=500.0, **kw,
    ).start()


def test_serve_bucket_contract_holds_and_guard_counts(graph):
    srv = _server(graph)
    try:
        srv.embed([1, 2, 3])  # warmup: ONE compile of the padded bucket
        srv.embed([4])
        srv.embed([5, 6, 7, 8, 9])
        c = _counters()
        assert c["serve_recompiles"] == 0  # fixed bucket: no recompiles
        # live drill: break the bucket contract -> BOTH counters fire
        # and the journal names the serve forward with the shape diff
        srv.max_batch = 4
        srv.embed([10, 11])
        c = _counters()
        assert c["serve_recompiles"] == 1
        assert c["device_recompiles"] == 1
        led = devprof.recompile_ledger()
        assert led and led[-1]["fn"] == "embed_step"
        assert any("8," in d and "4," in d for d in led[-1]["diff"])
        assert srv.stats()["devprof"]["serve_recompiles"] == 1
    finally:
        srv.close()


def test_serve_strict_bucket_raises_on_live_drill(graph):
    srv = _server(graph, strict_bucket=True)
    try:
        srv.embed([1, 2])  # warmup
        srv.max_batch = 4  # bucket contract broken
        with pytest.raises(devprof.RecompileError, match="embed_step"):
            srv.embed([3])
        assert _counters()["serve_recompiles"] == 1
    finally:
        srv.close()


def test_serve_slo_gauges_render(graph):
    from euler_tpu import telemetry as T

    srv = _server(graph)
    try:
        srv.embed([1, 2, 3])
        srv.slo.push_gauges()
        slo = T.telemetry_json()["serve_slo"]
        assert slo["count"] >= 1
        assert slo["p99_us"] >= slo["p50_us"] > 0
        text = T.metrics_text()
        assert 'eg_serve_slo_ms{quantile="p50"}' in text
        assert 'eg_serve_slo_ms{quantile="p99"}' in text
        assert "eg_serve_slo_violations_total" in text
    finally:
        srv.close()


# --------------------------------------------------- merged trace export


def test_merged_trace_has_aligned_device_lanes(tmp_path):
    import jax
    import jax.numpy as jnp

    from euler_tpu import trace as trace_mod

    f = jax.jit(lambda x: (x @ x.T).sum())
    x = jnp.ones((128, 128), jnp.float32)
    f(x).block_until_ready()  # compile outside the capture

    from euler_tpu.telemetry import record_phase

    rec = trace_mod.TraceRecorder().start()
    prof = str(tmp_path / "prof")
    t0 = trace_mod.now_us()
    jax.profiler.start_trace(prof)
    with trace_mod.align_annotation():
        pass
    for step in range(2):
        import time as _time

        t_dev = _time.perf_counter()
        jax.block_until_ready(f(x))
        record_phase("device", (_time.perf_counter() - t_dev) * 1e6,
                     step=step)
    jax.profiler.stop_trace()
    t1 = trace_mod.now_us()
    rec.stop()

    out = str(tmp_path / "trace.json")
    trace = trace_mod.write_trace(out, rec, profile_dir=prof)
    events = trace_mod.validate_chrome_trace(trace)
    dev = [e for e in events if e.get("cat") == "device"
           and e.get("ph") == "X"]
    host = [e for e in events if e.get("cat") == "phase"
            and e["name"] == "device"]
    assert dev and host
    assert all(e["pid"] >= trace_mod.PID_DEVICE_BASE for e in dev)
    assert all(e["pid"] == trace_mod.PID_TRAIN for e in host)
    # time alignment: every device slice falls inside the capture
    # window on the HOST clock (the eg_align marker did its job —
    # unaligned profiler timestamps sit ~minutes off)
    pad = 2_000_000
    assert all(t0 - pad <= e["ts"] <= t1 + pad for e in dev), dev[:3]
    # and the kernel slices overlap the host device-phase slices
    lo = min(e["ts"] for e in host)
    hi = max(e["ts"] + e["dur"] for e in host)
    assert any(lo - pad <= e["ts"] <= hi + pad for e in dev)


def test_ingest_missing_or_unstamped_dir(tmp_path):
    from euler_tpu import trace as trace_mod

    assert trace_mod.ingest_profiler_dir(str(tmp_path / "nope")) == []


# --------------------------------------------------------- config surface


def test_devprof_config_key_local_mode(fixture_dir):
    import euler_tpu

    g = euler_tpu.Graph(directory=fixture_dir, devprof="0")
    try:
        assert devprof.devprof_enabled() is False
    finally:
        devprof.set_devprof(True)
        g.close()
    g = euler_tpu.Graph(directory=fixture_dir, devprof="1")
    try:
        assert devprof.devprof_enabled() is True
    finally:
        g.close()


def test_compile_summary_keys():
    s = devprof.compile_summary()
    for k in ("compiles", "recompiles", "serve_recompiles",
              "compile_events", "compile_ms_total", "compile_ms_p50",
              "compile_ms_p99", "h2d_bytes", "d2h_bytes",
              "device_mem_bytes", "device_mem_peak_bytes",
              "device_buffers"):
        assert k in s, k
