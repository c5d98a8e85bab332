"""The reduction from a trace's planes to busy, idle, top operations."""

from benchmark import trace

MS = 1_000_000  # ns


def planes(ops_by_chip, modules=(), host=()):
    out = {}
    for i, ops in enumerate(ops_by_chip):
        out[f"/device:TPU:{i}"] = {"XLA Ops": list(ops), "XLA Modules": list(modules)}
    out["/host:CPU"] = {"main": list(host)}
    return out


def test_union_merges_overlaps_and_touching():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_overlapping_intervals_count_once():
    # a: 0-4 ms, b overlaps it 2-6 ms, gap 6-8, c: 8-10 ms
    p = planes([[("a", 0, 4 * MS), ("b", 2 * MS, 4 * MS), ("c", 8 * MS, 2 * MS)]],
               modules=[("jit_train_step(123)", 0, 6 * MS),
                        ("jit_train_step(123)", 8 * MS, 2 * MS),
                        ("jit_other(9)", 0, 1)],
               host=[("wait_for_batch", 6 * MS - 1000, 2 * MS + 2000),
                     ("whole_epoch", 0, 10 * MS), ("blip", 6 * MS, 10_000)])
    r = trace.reduce_planes(p, "jit_train_step")
    assert abs(r["busy_s"] - 0.008) < 1e-12
    assert abs(r["window_s"] - 0.010) < 1e-12
    assert abs(r["step_executions"] - (6 + 2) / 6) < 1e-12  # 6 ms whole, 2 ms cut
    assert r["device_ops"][0] == ["a", 0.004] or r["device_ops"][0][1] == 0.004
    # the gap takes the shortest host event that covers at least half of it
    assert r["idle_gaps"] == [["host:wait_for_batch", 0.002]]


def test_two_device_lines_are_averaged():
    p = planes([[("a", 0, 10 * MS)], [("a", 0, 4 * MS), ("a", 6 * MS, 4 * MS)]])
    r = trace.reduce_planes(p)
    assert r["chips"] == 2
    assert abs(r["busy_s"] - 0.009) < 1e-12   # (10 + 8) / 2 ms
    assert abs(r["window_s"] - 0.010) < 1e-12
    assert r["idle_gaps"] == [["before:a", 0.001]]  # 2 ms on one of two chips
    assert r["step_executions"] is None


def test_empty_window_gives_nothing():
    assert trace.reduce_planes({"/host:CPU": {"main": [("x", 0, 5)]}}) is None
    assert trace.reduce_planes(planes([[]])) is None
    assert trace.summarize("/nonexistent/trace/dir") is None


def test_reads_a_recorded_xplane(tmp_path):
    """A small trace recorded here has a host plane and no chip: the reader
    keeps the host's long events, the reduction returns nothing."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    assert path is not None
    read = trace.read_planes(path)
    assert trace.HOST_PLANE in read
    assert all(d >= 50_000 for line in read[trace.HOST_PLANE].values()
               for _, _, d in line)
    assert trace.reduce_planes(read) is None
