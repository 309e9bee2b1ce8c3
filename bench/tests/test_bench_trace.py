"""The trace reduction on hand-built traces: busy union, idle share, device
time by name, gaps named by the harness span that covers them, and the
reading of an xplane file that ``jax.profiler`` wrote."""
import pytest

import bench_tiny  # noqa: F401  (puts bench/ on the path)
from benchlib import trace as T

MS = 1_000_000


def _trace():
    # device: a loop [0,4) ms holding ops [0,1) and [1,3); an op [5,6)
    ops = [("%while.1", 0, 4 * MS), ("%fusion.1", 0, MS),
           ("paged_attention", 1 * MS, 2 * MS), ("%fusion.2", 5 * MS, MS)]
    modules = [("jit_chunk(42)", 0, 4 * MS), ("jit_refill(7)", 5 * MS, MS)]
    spans = [("bench.window", 0, 10 * MS),
             ("bench.boundary", 0, 4 * MS),
             ("bench.sleep_until_due", 4 * MS, MS),
             ("bench.boundary", 5 * MS, 5 * MS)]
    return T.Trace(ops=ops, modules=modules, spans=spans)


def test_union_and_busy():
    assert T.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    tr = _trace()
    w = T.span_window(tr, "bench.window")
    assert w == (0, 10 * MS)
    assert T.busy_s(tr, w) == pytest.approx(5e-3)
    assert T.idle_share(tr, w) == pytest.approx(0.5)
    # a window that cuts an interval counts only its part
    assert T.busy_s(tr, (2 * MS, 10 * MS)) == pytest.approx(3e-3)


def test_time_by_name_strips_module_ids():
    tr = _trace()
    mods = T.time_by_name(tr.modules)
    assert mods == {"jit_chunk": pytest.approx(4e-3),
                    "jit_refill": pytest.approx(1e-3)}
    assert T.time_by_name(T.matching(tr.ops, "paged_attention")) == {
        "paged_attention": pytest.approx(2e-3)}
    assert T.op_name("%fusion.3 = bf16[8]{0} fusion(%p)") == "%fusion.3"


def test_self_time_takes_out_nested_events():
    tr = _trace()
    self_s = {n: d / 1e9 for n, _, d in T.self_time(tr.ops)}
    assert self_s == {"%while.1": pytest.approx(1e-3),
                      "%fusion.1": pytest.approx(1e-3),
                      "paged_attention": pytest.approx(2e-3),
                      "%fusion.2": pytest.approx(1e-3)}
    top = T.top_ops(tr.ops)
    assert top[0] == ["paged_attention", pytest.approx(2e-3)]
    assert len(top) == 4


def test_in_window_keeps_whole_events():
    tr = _trace()
    assert [n for n, _, _ in T.in_window(tr.ops, (0, 4 * MS))] == [
        "%while.1", "%fusion.1", "paged_attention"]


def test_gaps_are_named_by_the_host_span():
    tr = _trace()
    w = T.span_window(tr, "bench.window")
    g = T.gaps(tr, w, ("bench.boundary", "bench.sleep_until_due"))
    # idle: [4,5) while the host slept, [6,10) in a boundary
    assert g == [["bench.boundary", pytest.approx(4e-3)],
                 ["bench.sleep_until_due", pytest.approx(1e-3)]]
    assert T.gaps(tr, w, ())[0][0] == "none"


def test_reads_a_recorded_xplane(tmp_path):
    jax = pytest.importorskip("jax")
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            jax.block_until_ready(jax.jit(lambda x: x * 2)(jax.numpy.ones(8)))
    tr = T.load(T.find_xplane(str(tmp_path)))
    assert T.span_window(tr, "bench.window") is not None
    with pytest.raises(FileNotFoundError):
        T.find_xplane(str(tmp_path / "none"))


def test_inside_keeps_the_events_within_a_program():
    kernels = [("k", 1 * MS, MS), ("k", 5 * MS, MS), ("k", 8 * MS, MS)]
    programs = [("jit_chunk(1)", 0, 4 * MS), ("jit_chunk(1)", 7 * MS, 3 * MS)]
    assert [s for _, s, _ in T.inside(kernels, programs)] == [MS, 8 * MS]


def test_roofline_counts_only_the_paged_kernel_on_paged_layers():
    """A second custom call inside ``jit_chunk`` is not the paged kernel,
    and each step's least time is counted once per paged layer."""
    import types
    from benchlib import readers, roofline, serve
    dims = types.SimpleNamespace(layers=4, paged_layers=1, heads=4,
                                 kv_heads=2, head_dim=16)
    ops = [("%paged_attention.10 tpu_custom_call", 1 * MS, MS),
           ("%local_attention.3 tpu_custom_call", 2 * MS, MS),
           ("%fusion.7", 3 * MS, MS // 2)]
    tr = T.Trace(ops=ops, modules=[("jit_chunk(3)", 0, 4 * MS)],
                 spans=[("bench.window", 0, 10 * MS)])
    step = [100, 200]
    b = serve.Boundary(t0=1.0, t1=2.0, tokens=2, status={}, stats={},
                       admitted=[], steps=[step])
    rec = readers.Record(window=(0.0, 10.0), end=10.0, reqs=[],
                         boundaries=[b], counted=[], dims=dims, rows=2,
                         page_size=16, kv_bytes=1, kind="TPU v5 lite",
                         trace=tr, trace_window=(0, 10 * MS))
    least, _ = roofline.least_time(
        roofline.paged_attention_flops(dims, step),
        roofline.paged_attention_bytes(dims, step, 16, 1),
        roofline.peaks("TPU v5 lite"))
    got = readers.paged_attention_roofline(rec)
    assert got == pytest.approx(100.0 * least / 1e-3)
    assert T.kernel_calls(ops, "paged_attention") == ops[:1]
