"""The trace reduction on a small hand-made trace, and ``load`` on a
real profiler file from the CPU."""
import pytest

from lsmbench import trace as tr

MS = 1e6   # ns


def _planes():
    host = {"name": "/host:CPU", "lines": [
        {"name": "client", "events": [
            ("lsmbench.window", 0 * MS, 100 * MS),
            ("lsmbench.get", 10 * MS, 20 * MS),
            ("lsmbench.scan", 60 * MS, 10 * MS)]},
        {"name": "pump", "events": [
            ("lsmbench.pump", 20 * MS, 30 * MS),
            ("other", 0, 5 * MS)]}]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ("jit_bloom_probe_multi_kernel(12)", 12 * MS, 6 * MS),
            ("jit__round(3)", 25 * MS, 10 * MS),     # starts in get+pump
            ("jit__round(4)", 62 * MS, 4 * MS),      # inside the scan
            ("jit__round(5)", 95 * MS, 10 * MS)]},   # crosses the end
        {"name": "XLA Ops", "events": [("fusion", 12 * MS, 99 * MS)]}]}
    return [host, dev]


def test_busy_and_window():
    red = tr.reduce(_planes())
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx((6 + 10 + 4 + 5) / 1e3)
    assert red["devices"] == 1


def test_programs_clipped_to_window_and_named_without_ids():
    red = tr.reduce(_planes())
    assert red["programs"]["jit__round"] == pytest.approx(0.019)
    assert red["programs"]["jit_bloom_probe_multi_kernel"] == \
        pytest.approx(0.006)


def test_programs_go_to_the_latest_started_span():
    red = tr.reduce(_planes())
    assert tr.device_seconds(red, "bloom_probe", span="get") == \
        pytest.approx(0.006)
    # 25 ms lies in the get (10-30) and the pump (20-50): pump started last
    assert tr.device_seconds(red, "_round", span="pump") == \
        pytest.approx(0.010)
    assert tr.device_seconds(red, "_round", span="scan") == \
        pytest.approx(0.004)
    assert tr.device_seconds(red, "_round", span="idle") == \
        pytest.approx(0.005)


def test_gaps_labelled_by_host_span():
    red = tr.reduce(_planes())
    gaps = sorted(red["gaps"], key=lambda g: -g[1])
    assert gaps[0] == ("idle", pytest.approx(0.029))       # 66-95 ms
    assert ("pump", pytest.approx(0.027)) in gaps           # 35-62 ms
    assert ("pump", pytest.approx(0.007)) in gaps           # 18-25 ms
    assert sum(g for _, g in gaps) == pytest.approx(0.075)
    bd = tr.breakdown(red, n=2)
    assert bd["idle_gaps"][0][0] == "host:idle"
    assert bd["device_ops"][0] == ["jit__round", pytest.approx(0.019)]
    assert len(bd["idle_gaps"]) == 2


def test_no_window_is_an_error():
    planes = _planes()
    planes[0]["lines"][0]["events"].pop(0)
    with pytest.raises(ValueError):
        tr.reduce(planes)


def test_load_reads_a_profiler_file(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    f = jax.jit(lambda x: x * 2)
    x = jnp.ones(8)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with TraceAnnotation("lsmbench.window"):
        with TraceAnnotation("lsmbench.get"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    planes = tr.load(next(tmp_path.rglob("*.xplane.pb")))
    red = tr.reduce(planes)
    assert red["window_s"] > 0
    names = {ev[0] for p in planes for ln in p["lines"]
             for ev in ln["events"]}
    assert "lsmbench.get" in names
