"""Fault-tolerance substrate of the resilient engine: the replica
supervisor (heartbeats, stragglers, fencing) and elastic mesh planning."""

from repro.distributed.elastic import plan_mesh
from repro.distributed.fault import Supervisor


# -- supervisor --------------------------------------------------------------


def test_supervisor_failure_detection():
    clock = [0.0]
    sup = Supervisor(4, timeout=10.0, clock=lambda: clock[0])
    for h in range(4):
        sup.beat(h, 1)
    clock[0] = 5.0
    for h in (0, 1, 2):
        sup.beat(h, 2)
    assert sup.dead_hosts() == []
    clock[0] = 12.0     # host 3 last beat at t=0 -> dead; 0-2 beat at t=5
    assert sup.dead_hosts() == [3]
    plan = sup.restart_plan(spare_hosts=0)
    assert plan["action"] == "shrink" and plan["new_size"] == 3
    plan = sup.restart_plan(spare_hosts=2)
    assert plan["action"] == "replace"


def test_supervisor_straggler_detection():
    clock = [0.0]
    sup = Supervisor(4, timeout=1e9, straggler_factor=2.0,
                     clock=lambda: clock[0])
    # hosts 0-2 step every 1s; host 3 every 10s
    for step in range(1, 6):
        for h in (0, 1, 2):
            clock[0] = step * 1.0
            sup.beat(h, step)
        clock[0] = step * 10.0
        sup.beat(3, step)
    assert sup.stragglers() == [3]
    assert sup.fleet_step() == 5


# -- elastic -------------------------------------------------------------------


def test_plan_mesh_shrink():
    p = plan_mesh(512, model_parallel=16, want_pods=2)
    assert p.shape == (2, 16, 16)
    p = plan_mesh(256, model_parallel=16)
    assert p.shape == (16, 16)
    # lost 16 hosts of 32 on one pod: 240 devices
    p = plan_mesh(240, model_parallel=16)
    assert p.shape == (15, 16) and p.note == ""
    # awkward count: drops stragglers
    p = plan_mesh(250, model_parallel=16)
    assert p.n_devices <= 250


def test_plan_mesh_awkward_counts():
    # prime count: model axis folds down to 1, everything becomes data
    p = plan_mesh(7, model_parallel=16)
    assert p.shape == (7, 1) and p.n_devices == 7
    # single device
    p = plan_mesh(1, model_parallel=16)
    assert p.n_devices == 1
    # non-dividing want_pods falls back to a 2-axis mesh
    p = plan_mesh(256, model_parallel=16, want_pods=3)
    assert p.axes == ("data", "model")


# -- fencing epoch (PR 8) -----------------------------------------------------


def test_fence_rejects_zombie_beats():
    clock = [0.0]
    sup = Supervisor(4, timeout=10.0, clock=lambda: clock[0])
    for h in range(4):
        sup.beat(h, 1)
    clock[0] = 20.0
    for h in (0, 1, 2):
        sup.beat(h, 2)
    plan = sup.restart_plan(fence=True)
    assert plan["action"] == "shrink" and plan["dead"] == [3]
    assert sup.fenced() == [3]
    # the zombie process keeps beating: no epoch, then a stale epoch —
    # neither may flip the host back to alive
    assert sup.beat(3, 3) is False
    assert sup.beat(3, 3, epoch=0) is False
    assert sup.rejected_beats == 2
    assert sup.fenced() == [3]
    assert 3 not in [h for h in sup.hosts if sup.hosts[h].alive]


def test_fence_readmission_epoch():
    clock = [0.0]
    sup = Supervisor(2, timeout=5.0, clock=lambda: clock[0])
    sup.fence([1])
    ep = sup.hosts[1].epoch
    # a beat carrying the CURRENT epoch is the re-admission handshake
    assert sup.beat(1, 7, epoch=ep) is True
    assert sup.fenced() == [] and sup.hosts[1].alive
    # coordinator-side readmit: refreshes the beat clock too
    sup.fence([0])
    clock[0] = 3.0
    assert sup.readmit(0) == sup.hosts[0].epoch
    assert sup.fenced() == [] and sup.hosts[0].last_beat == 3.0


def test_restart_plan_fencing_is_idempotent():
    clock = [0.0]
    sup = Supervisor(3, timeout=1.0, clock=lambda: clock[0])
    clock[0] = 5.0
    sup.beat(0, 1)
    p1 = sup.restart_plan(fence=True)
    epochs = {h: sup.hosts[h].epoch for h in (1, 2)}
    # a second sweep sees the same dead set and must not bump epochs again
    p2 = sup.restart_plan(fence=True)
    assert p1["dead"] == p2["dead"] == [1, 2]
    assert {h: sup.hosts[h].epoch for h in (1, 2)} == epochs
    # default restart_plan never fences (pre-PR-8 behavior preserved)
    sup2 = Supervisor(2, timeout=1.0, clock=lambda: clock[0])
    clock[0] = 10.0
    assert sup2.restart_plan()["dead"] == [0, 1]
    assert sup2.fenced() == []
    assert sup2.beat(0, 1) is True
