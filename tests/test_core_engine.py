"""Unit tests for the MMA core: chunking, queues, path selection,
dummy-task semantics, backpressure and fallback."""
import pytest

from repro.core import (
    Direction,
    DummyTask,
    MMAConfig,
    MicroTaskQueue,
    Route,
    SimStream,
    SimWorld,
    TaskManager,
    TaskState,
    TransferTask,
    make_sim_engine,
)
from repro.core.config import MB, GB
from repro.core.simlink import BackgroundFlow
from repro.core.transfer_task import MicroTask


# ---------------------------------------------------------------------------
# Task manager / chunking
# ---------------------------------------------------------------------------
def test_split_exact_chunks():
    tm = TaskManager(MMAConfig(chunk_bytes=5 * MB))
    t = TransferTask(nbytes=20 * MB, target=0, direction=Direction.H2D)
    micro = tm.split(t)
    assert len(micro) == 4
    assert all(m.nbytes == 5 * MB for m in micro)
    assert [m.offset for m in micro] == [0, 5 * MB, 10 * MB, 15 * MB]


def test_split_ragged_tail():
    tm = TaskManager(MMAConfig(chunk_bytes=5 * MB))
    t = TransferTask(nbytes=12 * MB + 123, target=3, direction=Direction.D2H)
    micro = tm.split(t)
    assert len(micro) == 3
    assert sum(m.nbytes for m in micro) == t.nbytes
    assert micro[-1].nbytes == 2 * MB + 123
    assert all(m.dest == 3 for m in micro)


def test_completion_fires_once_after_all_chunks():
    tm = TaskManager(MMAConfig(chunk_bytes=1 * MB))
    fired = []
    tm.add_completion_listener(lambda task: fired.append(task.task_id))
    t = TransferTask(nbytes=3 * MB, target=0, direction=Direction.H2D)
    micro = tm.split(t)
    for i, m in enumerate(micro):
        assert not fired
        tm.micro_task_done(m, now=float(i))
    assert fired == [t.task_id]
    assert t.state == TaskState.COMPLETE
    assert t.complete_time == 2.0


# ---------------------------------------------------------------------------
# Micro-task queue policies
# ---------------------------------------------------------------------------
def _mt(dest, nbytes=1 * MB, seq=0):
    t = TransferTask(nbytes=nbytes, target=dest, direction=Direction.H2D)
    return MicroTask(parent=t, offset=0, nbytes=nbytes, seq=seq)


def test_longest_remaining_destination():
    q = MicroTaskQueue()
    for _ in range(2):
        q.push(_mt(dest=1))
    for _ in range(5):
        q.push(_mt(dest=2))
    assert q.longest_remaining_dest(exclude=0) == 2
    assert q.longest_remaining_dest(exclude=2) == 1
    # draining dest 2 flips the answer
    for _ in range(4):
        q.pop_for_dest(2)
    assert q.longest_remaining_dest(exclude=0) == 1


def test_queue_remaining_bytes_tracking():
    q = MicroTaskQueue()
    q.push(_mt(dest=0, nbytes=3 * MB))
    q.push(_mt(dest=0, nbytes=1 * MB))
    assert q.remaining_bytes(0) == 4 * MB
    q.pop_for_dest(0)
    assert q.remaining_bytes(0) == 1 * MB
    assert q.pop_for_dest(1) is None


# ---------------------------------------------------------------------------
# Path selection
# ---------------------------------------------------------------------------
def test_direct_priority_routes_own_dest_first():
    eng, world, _ = make_sim_engine()
    t = eng.memcpy(100 * MB, device=0, direction=Direction.H2D)
    world.run()
    w0 = eng.workers[0]
    assert w0.chunks_direct > 0
    assert w0.chunks_relay == 0  # only one destination exists
    # other workers only relayed
    for d in range(1, 8):
        assert eng.workers[d].chunks_direct == 0


def test_relay_restriction_respected():
    cfg = MMAConfig()
    eng, world, _ = make_sim_engine(config=cfg)
    eng.set_relay_devices([1, 2])
    eng.memcpy(200 * MB, device=0, direction=Direction.H2D)
    world.run()
    for d in range(3, 8):
        assert eng.workers[d].chunks_relay == 0
    assert eng.workers[1].chunks_relay > 0
    assert eng.workers[2].chunks_relay > 0


def test_numa_local_only_mode():
    cfg = MMAConfig(numa_local_only=True)
    eng, world, _ = make_sim_engine(config=cfg)
    eng.memcpy(200 * MB, device=0, direction=Direction.H2D)
    world.run()
    # devices 4-7 are on NUMA 1; target 0 is NUMA 0
    for d in range(4, 8):
        assert eng.workers[d].chunks_relay == 0


def test_route_is_direct():
    assert Route(link_dev=3, dest=3).is_direct
    assert not Route(link_dev=1, dest=3).is_direct


# ---------------------------------------------------------------------------
# Fallback threshold (paper §3.2)
# ---------------------------------------------------------------------------
def test_small_transfer_falls_back_to_native():
    eng, world, _ = make_sim_engine()
    t = eng.memcpy(1 * MB, device=0, direction=Direction.H2D)
    world.run()
    assert eng.stats.fallback_transfers == 1
    assert t.state == TaskState.COMPLETE
    # no chunks went through the multipath workers
    assert all(w.bytes_total == 0 for w in eng.workers.values())


def test_large_transfer_uses_multipath():
    eng, world, _ = make_sim_engine()
    t = eng.memcpy(1 * GB, device=0, direction=Direction.H2D)
    world.run()
    assert eng.stats.fallback_transfers == 0
    assert t.state == TaskState.COMPLETE
    relay_bytes = sum(
        w.bytes_total for d, w in eng.workers.items() if d != 0
    )
    assert relay_bytes > 0


# ---------------------------------------------------------------------------
# Dummy task / stream semantics (paper C2)
# ---------------------------------------------------------------------------
def test_downstream_compute_waits_for_multipath_completion():
    eng, world, _ = make_sim_engine()
    stream = SimStream(world)
    dummy = eng.memcpy_async(1 * GB, device=0, direction=Direction.H2D)
    stream.dummy(dummy, label="copy")
    stream.compute(1e-3, label="kernel")
    world.run()
    t_copy = stream.completion_time("copy")
    t_kernel = stream.completion_time("kernel")
    assert t_copy is not None and t_kernel is not None
    assert t_kernel >= t_copy + 1e-3  # kernel ran strictly after the copy
    assert dummy.task.state == TaskState.COMPLETE
    # the dummy released exactly at transfer completion
    assert t_copy == pytest.approx(dummy.task.complete_time, rel=1e-9)


def test_dispatch_deferred_until_stream_reaches_dummy():
    """C1: path selection/dispatch must not begin before the stream reaches
    the copy point."""
    eng, world, _ = make_sim_engine()
    stream = SimStream(world)
    dummy = eng.memcpy_async(100 * MB, device=0, direction=Direction.H2D)
    stream.compute(5e-3, label="pre")   # 5 ms of upstream work
    stream.dummy(dummy, label="copy")
    world.run()
    # Transfer submit time is stamped at activation — after the 5ms compute.
    assert dummy.task.submit_time >= 5e-3


def test_dummy_completion_before_reach_releases_immediately():
    task = TransferTask(nbytes=1, target=0, direction=Direction.H2D)
    dummy = DummyTask(task=task, on_activate=lambda t: None)
    dummy.complete()  # transfer done before stream reaches the dummy
    released = []

    class W:
        def release(self):
            released.append(1)

    dummy.reach(W())
    assert released == [1]


def test_two_streams_independent():
    """Independent streams must not serialize on each other's dummies."""
    eng, world, _ = make_sim_engine()
    s1, s2 = SimStream(world, "s1"), SimStream(world, "s2")
    d1 = eng.memcpy_async(2 * GB, device=0, direction=Direction.H2D)
    s1.dummy(d1, label="big_copy")
    s2.compute(1e-4, label="small_kernel")
    world.run()
    # s2's kernel finishes long before s1's big copy
    assert s2.completion_time("small_kernel") < s1.completion_time("big_copy")


# ---------------------------------------------------------------------------
# Backpressure & contention backoff (paper C3)
# ---------------------------------------------------------------------------
def test_backpressure_shifts_work_off_congested_link():
    cfg = MMAConfig()
    eng, world, backend = make_sim_engine(config=cfg)
    # Congest relay GPU 1's PCIe H2D link with background native traffic.
    BackgroundFlow(
        world,
        stages=[(backend.dram[0], 1.0), (backend.pcie_h2d[1], 1.0)],
        t_start=0.0,
    )
    eng.memcpy(2 * GB, device=0, direction=Direction.H2D)
    world.run(until=0.2)
    w1 = eng.workers[1]
    w2 = eng.workers[2]
    # Congested link carried (much) less relay work than its uncontended twin
    assert w1.bytes_total < 0.75 * w2.bytes_total


def _launch_depths(backend, worker):
    """Record ``worker.outstanding`` (this chunk included) at every launch."""
    depths = []
    launch = backend.launch

    def spy(mt, route, on_done):
        depths.append(worker.outstanding)
        return launch(mt, route, on_done)

    backend.launch = spy
    return depths


def _copy(eng, world, direction, nbytes=250 * MB):
    task = eng.memcpy(nbytes, device=0, direction=direction)
    while task.state != TaskState.COMPLETE:
        world.run(until=world.now + 1e-3)


def test_d2h_after_fast_h2d_history_pulls_to_full_depth():
    """A link whose D2H moves a byte 5x slower than its H2D: the D2H
    chunks are judged against D2H history alone, so after a fast H2D the
    worker still keeps two D2H chunks outstanding and is not contended."""
    eng, world, backend = make_sim_engine(
        config=MMAConfig(fallback_bytes=0), devices=[0])
    backend.set_link_degradation("pcie_d2h", 0, multiplier=0.2)
    w = eng.workers[0]
    _copy(eng, world, Direction.H2D)
    depths = _launch_depths(backend, w)
    _copy(eng, world, Direction.D2H)
    assert not w.contended
    assert depths[0] == 1 and set(depths[1:]) == {2}, depths


def test_link_congested_in_one_direction_backs_off_in_that_direction():
    """Native traffic on the D2H wire: the worker's D2H chunks slow past
    ``backoff_factor`` and it pulls at depth 1; its H2D chunks, on the
    idle other direction, are not held back by it."""
    eng, world, backend = make_sim_engine(
        config=MMAConfig(fallback_bytes=0), devices=[0])
    w = eng.workers[0]
    for direction in (Direction.H2D, Direction.D2H):
        _copy(eng, world, direction, 64 * MB)
        assert not w.contended
    BackgroundFlow(world, stages=[(backend.pcie_d2h[0], 1.0)],
                   t_start=world.now, t_stop=world.now + 1.0)
    depths = _launch_depths(backend, w)
    _copy(eng, world, Direction.D2H)
    assert w.contended
    assert depths.count(1) > 0.9 * len(depths), depths
    del depths[:]
    _copy(eng, world, Direction.H2D)
    assert not w.contended
    assert depths.count(2) > 0.9 * len(depths), depths


def test_outstanding_queue_capacity_respected():
    cfg = MMAConfig(queue_depth=2)
    eng, world, _ = make_sim_engine(config=cfg)
    eng.memcpy(1 * GB, device=0, direction=Direction.H2D)
    # At any event boundary no worker may exceed its outstanding cap.
    for _ in range(200):
        world.run(until=world.now + 1e-4)
        for w in eng.workers.values():
            assert w.outstanding <= cfg.queue_depth
        if world.idle():
            break


def test_concurrent_mma_flows_share_fairly():
    """Fig 9b: two concurrent MMA flows both far exceed native, neither
    collapses."""
    from repro.core.engine import MMAEngine
    from repro.core.task_launcher import SimBackend
    from repro.core.topology import h20_server

    topo = h20_server()
    world = SimWorld()
    cfg1, cfg2 = MMAConfig(), MMAConfig()
    backend = SimBackend(world, topo, cfg1)
    e1 = MMAEngine(topo, backend, cfg1)
    e2 = MMAEngine(topo, backend, cfg2)
    t1 = e1.memcpy(1 * GB, device=0, direction=Direction.H2D)
    t2 = e2.memcpy(1 * GB, device=1, direction=Direction.H2D)
    world.run()
    bw1, bw2 = t1.bandwidth_gbps(), t2.bandwidth_gbps()
    native = 53.6
    assert bw1 > 1.5 * native and bw2 > 1.5 * native
    assert 0.5 < bw1 / bw2 < 2.0  # rough fairness


def test_sync_copy_blocks_semantics():
    eng, world, _ = make_sim_engine()
    t = eng.memcpy(500 * MB, device=2, direction=Direction.D2H)
    assert t.sync
    world.run()
    assert t.state == TaskState.COMPLETE
    assert t.complete_time > t.submit_time


def test_engine_stats_accumulate():
    eng, world, _ = make_sim_engine()
    eng.memcpy(1 * MB, device=0)
    eng.memcpy(100 * MB, device=1)
    world.run()
    assert eng.stats.transfers == 2
    assert eng.stats.fallback_transfers == 1
    assert eng.stats.bytes_total == 101 * MB


def test_cpu_overhead_model_matches_paper():
    eng, _, _ = make_sim_engine()
    # Paper Fig 11: ~8.2 equivalent cores at 8 active GPUs, linear.
    assert eng.estimated_cpu_cores(8) == pytest.approx(8.2, rel=0.05)
    assert eng.estimated_cpu_cores(4) == pytest.approx(4.1, rel=0.05)


# ---------------------------------------------------------------------------
# Topology relay discovery
# ---------------------------------------------------------------------------
def test_relay_candidates_excludes_target_and_exclude_set():
    from repro.core.topology import h20_server

    topo = h20_server()
    peers = topo.relay_candidates(target=2)
    assert 2 not in peers
    assert sorted(peers) == [0, 1, 3, 4, 5, 6, 7]
    peers = topo.relay_candidates(target=2, exclude=(0, 5))
    assert set(peers).isdisjoint({0, 2, 5})
    assert sorted(peers) == [1, 3, 4, 6, 7]
    # excluding the target itself is a no-op (it is never a candidate)
    assert topo.relay_candidates(target=2, exclude=(2,)) == (
        topo.relay_candidates(target=2)
    )


def test_relay_candidates_numa_local_only_filter():
    from repro.core.topology import h20_server

    topo = h20_server()     # devices 0-3 on NUMA 0, 4-7 on NUMA 1
    assert topo.relay_candidates(target=1, numa_local_only=True) == [0, 2, 3]
    assert topo.relay_candidates(target=6, numa_local_only=True) == [4, 5, 7]
    # exclusions compose with the NUMA filter
    assert topo.relay_candidates(
        target=1, numa_local_only=True, exclude=(2,)
    ) == [0, 3]


def test_relay_candidates_numa_first_ordering():
    from repro.core.topology import h20_server

    topo = h20_server()
    peers = topo.relay_candidates(target=5)
    # same-NUMA peers (4, 6, 7) come before cross-socket ones (0-3),
    # each group in index order
    assert peers == [4, 6, 7, 0, 1, 2, 3]
    # single-socket topology: ordering degenerates to plain index order
    from repro.core.topology import tpu_host

    assert tpu_host(4).relay_candidates(target=0) == [1, 2, 3]


# ---------------------------------------------------------------------------
# Zero-byte copies (edge path: zero micro-tasks)
# ---------------------------------------------------------------------------
def test_zero_byte_memcpy_completes_inline():
    eng, world, _ = make_sim_engine()
    t = eng.memcpy(0, device=3, direction=Direction.D2H)
    assert t.state == TaskState.COMPLETE
    assert t.complete_time == t.submit_time
    assert eng.task_manager.pending_transfers() == 0
    world.run()
    assert eng.stats.transfers == 1 and eng.stats.bytes_total == 0


def test_zero_byte_memcpy_async_releases_stream():
    """A zero-byte async copy splits into zero micro-tasks; its Dummy
    Task must still release the stream exactly at the copy point rather
    than blocking it forever."""
    eng, world, _ = make_sim_engine()
    stream = SimStream(world)
    done = []
    dummy = eng.memcpy_async(
        0, device=0, direction=Direction.H2D,
        on_complete=lambda t: done.append(t.task_id),
    )
    stream.dummy(dummy, label="empty")
    stream.compute(1e-4, label="kernel")
    world.run()
    assert dummy.task.state == TaskState.COMPLETE
    assert dummy.released
    assert done == [dummy.task.task_id]
    assert stream.completion_time("kernel") is not None
    assert eng.sync_engine.pending() == 0
