"""Placement layer: slot->node ownership, pack/spread strategies, cordon +
drain semantics, node-exact spot kills, drain-aware scale-down, and the
node-aware live operator (stub trainers — no JAX needed)."""
import pytest

from repro.cloud import (AutoscalerConfig, CloudProvider, CloudSimulator,
                         NodeAutoscaler, NodePool, SPOT)
from repro.core.cluster import Cluster
from repro.core.job import JobSpec, JobState, JobStatus
from repro.core.operator import ElasticClusterController
from repro.core.perf_model import PiecewiseScalingModel, RescaleModel
from repro.core.placement import PlacementError, PlacementMap
from repro.core.policies import PolicyConfig
from repro.core.simulator import Simulator, SimWorkload


def wl(steps=100.0, t1=1.0, t_many=1.0, data=1e9):
    return SimWorkload(
        scaling=PiecewiseScalingModel(((1.0, t1), (64.0, t_many))),
        total_work=steps, data_bytes=data, rescale=RescaleModel())


# ---------------------------------------------------------------------------
# PlacementMap primitives
# ---------------------------------------------------------------------------

def _two_nodes(strategy):
    p = PlacementMap(strategy)
    p.add_node("n0", 4)
    p.add_node("n1", 4)
    return p


def test_pack_fills_fullest_node_first():
    p = _two_nodes("pack")
    p.place("a", 2)                       # n0: a,a,_,_
    p.place("b", 3)                       # fills n0, overflows 1 to n1
    assert p.residents("n0") == {"a": 2, "b": 2}
    assert p.residents("n1") == {"b": 1}


def test_spread_round_robins_emptiest_first():
    p = _two_nodes("spread")
    p.place("a", 2)
    assert p.job_nodes("a") == {"n0": 1, "n1": 1}
    p.place("b", 4)
    assert p.job_nodes("b") == {"n0": 2, "n1": 2}


def test_place_is_all_or_nothing():
    p = _two_nodes("pack")
    p.place("a", 7)
    with pytest.raises(PlacementError):
        p.place("b", 2)
    assert p.owned("b") == 0              # nothing partially assigned
    p.place("b", 1)
    assert p.free() == 0


def test_no_double_ownership_across_ops():
    p = _two_nodes("pack")
    p.place("a", 3)
    p.place("b", 4)
    p.evict("a", 1)
    p.place("c", 2)
    owners = {}
    for nid in p.nodes():
        for job, cnt in p.residents(nid).items():
            owners[job] = owners.get(job, 0) + cnt
    assert owners == {"a": 2, "b": 4, "c": 2}
    assert sum(owners.values()) + p.free() == 8
    p.check()


def test_cordon_excludes_capacity_and_placement():
    p = _two_nodes("pack")
    p.place("a", 4)                       # fills n0
    p.cordon("n1")
    assert p.total_capacity == 4
    assert p.free() == 0
    with pytest.raises(PlacementError):
        p.place("b", 1)
    p.uncordon("n1")
    assert p.free() == 4


def test_evict_vacates_cordoned_node_first():
    p = _two_nodes("pack")
    p.place("a", 6)                       # n0 full, n1 holds 2
    p.cordon("n0")
    freed = p.evict("a", 4)
    assert p.residents("n0") == {}        # the draining node emptied first
    assert p.residents("n1") == {"a": 2}
    assert len(freed) == 4


def test_remove_node_refuses_residents_then_succeeds():
    p = _two_nodes("pack")
    p.place("a", 2)
    with pytest.raises(PlacementError):
        p.remove_node("n0")
    p.evict("a")
    assert p.remove_node("n0") == 4
    assert p.node_count == 1


def test_migrate_moves_residents_off_node():
    p = _two_nodes("pack")
    p.place("a", 3)                       # all on n0
    assert p.migrate("a", "n0") == 3
    assert p.residents("n0") == {}
    assert p.residents("n1") == {"a": 3}
    # b: pack tops up n1's last slot, overflows 3 onto n0
    p.place("b", 4)
    assert p.job_nodes("b") == {"n0": 3, "n1": 1}
    # the only free slot left sits ON n0 itself -> nothing can move off it
    assert p.free() == 1 and p.free("n0") == 1
    assert p.migrate("b", "n0") == 0


def test_fragmentation_pack_vs_spread():
    pack, spread = _two_nodes("pack"), _two_nodes("spread")
    pack.place("a", 2)
    spread.place("a", 2)
    # pack strands 2 free slots on n0; n1 stays whole-node free
    assert pack.fragmentation() == pytest.approx(2 / 6)
    # spread strands ALL free capacity on partially-used nodes
    assert spread.fragmentation() == pytest.approx(1.0)
    empty = _two_nodes("pack")
    assert empty.fragmentation() == 0.0


# ---------------------------------------------------------------------------
# Cluster integration
# ---------------------------------------------------------------------------

def test_cluster_base_capacity_partitions_into_nodes():
    c = Cluster(10, slots_per_node=4)
    assert c.nodes() == ["base00", "base01", "base02"]
    assert c.total_slots == 10            # last node holds the 2-slot tail
    c2 = Cluster(4)
    assert c2.nodes() == ["base"]


def test_cluster_residency_tracks_used_slots():
    sim = Simulator(16, PolicyConfig(rescale_gap=0.0), slots_per_node=8)
    sim.submit(JobSpec("a", 1, 4, 8, 0.0), wl(50))
    sim.submit(JobSpec("b", 2, 4, 8, 1.0), wl(50))
    sim.run()
    # after completion everything is evicted
    assert sim.cluster.used_slots == 0
    assert all(not sim.cluster.residents(n) for n in sim.cluster.nodes())


# ---------------------------------------------------------------------------
# CloudSimulator: node-exact spot kills (acceptance criterion)
# ---------------------------------------------------------------------------

def _spot_prov(nodes=3, slots=8, lifetime=1e12):
    return CloudProvider([NodePool(
        "sp", slots_per_node=slots, market=SPOT, initial_nodes=nodes,
        max_nodes=nodes, spot_lifetime_mean=lifetime)])


def test_spot_kill_displaces_only_killed_nodes_residents():
    prov = _spot_prov(nodes=3)
    sim = CloudSimulator(prov, PolicyConfig(rescale_gap=0.0))
    # three rigid 8-slot jobs -> pack pins one per node
    for i in range(3):
        sim.submit(JobSpec(f"j{i}", 1, 8, 8, float(i) * 0.001), wl(500))
    victim_node = sorted(prov.nodes)[1]

    resident_snapshot = {}
    # snapshot residency the instant the kill lands, then let it proceed
    prov.inject_spot_kill(victim_node, 10.0, sim.queue)
    orig = sim._on_spot_kill

    def probed(node_id):
        resident_snapshot.update(sim.cluster.residents(node_id))
        orig(node_id)
    sim._on_spot_kill = probed
    sim.run()
    assert len(resident_snapshot) == 1    # exactly one job lived there
    (victim_job,) = resident_snapshot
    for i in range(3):
        j = sim.cluster.jobs[f"j{i}"]
        if j.job_id == victim_job:
            assert j.preempt_count == 1   # rigid: checkpoint-preempted
        else:
            assert j.preempt_count == 0   # bystanders untouched
            assert j.rescale_count == 0
    assert sim.spot_victim_jobs == 1
    assert sim.kill_blasts == [(1, 8, 1, "default-a")]


def test_spot_kill_migrates_residents_when_free_capacity_exists():
    prov = _spot_prov(nodes=3)
    sim = CloudSimulator(prov, PolicyConfig(rescale_gap=0.0))
    sim.submit(JobSpec("a", 1, 8, 8, 0.0), wl(300))   # one node, rigid
    # pack places on the first bootstrapped node; kill exactly that one
    victim = sorted(prov.nodes)[0]
    prov.inject_spot_kill(victim, 10.0, sim.queue)
    m = sim.run()
    a = sim.cluster.jobs["a"]
    # two empty nodes remained -> workers migrated, no shrink, no preempt
    assert a.preempt_count == 0 and a.rescale_count == 0
    assert sim.migrations == 1
    assert a.status is JobStatus.COMPLETED
    assert m.kill_blast_jobs == 1.0
    assert m.kill_blast_radius == pytest.approx(8.0)
    assert m.kill_preemptions == 0.0
    # migration pays an overhead: slower than the 300 s solo runtime
    assert a.end_time > 300.0


def test_spot_kill_shrink_prefers_killed_node_over_other_cordoned():
    """With another node cordoned (an in-flight drain), a kill's forced
    shrink must still come off the KILLED node, not the draining one —
    otherwise the victim pays a shrink AND a preemption."""
    prov = _spot_prov(nodes=3)
    sim = CloudSimulator(prov, PolicyConfig(rescale_gap=0.0))
    job = JobState(spec=JobSpec("a", 1, 8, 24, 0.0), work_remaining=100.0)
    sim.workloads["a"] = wl(100)
    sim.cluster.add_job(job)
    assert sim.actions.create(job, 24)        # spans all three nodes
    nodes = sorted(prov.nodes)
    sim.cluster.cordon(nodes[2])              # unrelated drain in flight
    prov.inject_spot_kill(nodes[0], 10.0, sim.queue)
    sim.run()
    a = sim.cluster.jobs["a"]
    assert a.preempt_count == 0               # shrink absorbed the kill
    assert a.rescale_count == 1
    assert sim.kill_blasts == [(1, 8, 0, "default-a")]


def test_spot_kill_shrink_comes_off_killed_node_exactly():
    prov = _spot_prov(nodes=2)
    sim = CloudSimulator(prov, PolicyConfig(rescale_gap=0.0))
    sim.submit(JobSpec("a", 1, 4, 16, 0.0), wl(100))  # elastic 16 across both
    victim = sorted(prov.nodes)[0]
    prov.inject_spot_kill(victim, 20.0, sim.queue)
    m = sim.run()
    a = sim.cluster.jobs["a"]
    assert a.preempt_count == 0 and a.rescale_count == 1
    assert m.dropped_jobs == 0
    assert sim.kill_blasts == [(1, 8, 0, "default-a")]


# ---------------------------------------------------------------------------
# Drain-aware decommission + autoscaler scale-down
# ---------------------------------------------------------------------------

def test_decommission_returns_false_on_occupied_node():
    prov = _spot_prov(nodes=2)
    sim = CloudSimulator(prov, PolicyConfig(rescale_gap=0.0))
    job = JobState(spec=JobSpec("a", 1, 8, 8, 0.0))
    sim.workloads["a"] = wl(200)
    sim.cluster.add_job(job)
    assert sim.actions.create(job, 8)
    occupied = [n for n in sim.cluster.nodes() if sim.cluster.residents(n)]
    empty = [n for n in sim.cluster.nodes() if not sim.cluster.residents(n)]
    assert sim.decommission(occupied[0]) is False     # guarded, no crash
    assert sim.decommission(empty[0]) is True


def test_autoscaler_drains_min_residency_node_via_migration():
    prov = CloudProvider([NodePool("od", slots_per_node=8, boot_latency=30.0,
                                   teardown_delay=10.0, initial_nodes=3,
                                   max_nodes=3)])
    asc = NodeAutoscaler(prov, AutoscalerConfig(
        tick_interval=15.0, scale_up_cooldown=15.0, scale_down_cooldown=30.0,
        idle_timeout=60.0))
    sim = CloudSimulator(prov, PolicyConfig(rescale_gap=0.0), autoscaler=asc)
    # one long rigid 4-slot job: 20 of 24 slots idle, but under `pack` the
    # job pins one node; the other two are empty and must be released; the
    # job's own node must NOT be (its resident cannot migrate forever —
    # free capacity shrinks to zero as nodes retire)
    sim.submit(JobSpec("a", 1, 4, 4, 0.0), wl(1500))
    m = sim.run()
    assert sim.cluster.jobs["a"].status is JobStatus.COMPLETED
    assert asc.scale_downs == 2
    assert sim.cluster.jobs["a"].preempt_count == 0


def test_drain_migrates_then_releases_partially_used_node():
    prov = CloudProvider([NodePool("od", slots_per_node=8, boot_latency=30.0,
                                   teardown_delay=10.0, initial_nodes=2,
                                   max_nodes=2)])
    asc = NodeAutoscaler(prov, AutoscalerConfig(
        tick_interval=15.0, scale_up_cooldown=15.0, scale_down_cooldown=30.0,
        idle_timeout=60.0))
    sim = CloudSimulator(prov, PolicyConfig(rescale_gap=0.0), autoscaler=asc,
                         placement="spread")
    # spread puts 2+2 on the two nodes; scale-down must pick one, migrate its
    # 2 residents to the survivor, and release it
    sim.submit(JobSpec("a", 1, 4, 4, 0.0), wl(1200))
    m = sim.run()
    assert sim.cluster.jobs["a"].status is JobStatus.COMPLETED
    assert asc.scale_downs == 1
    assert sim.migrations >= 1
    assert sim.cluster.jobs["a"].preempt_count == 0
    assert m.total_cost < 2 * 8 * (1300 / 3600) * 0.048  # beat static-2


# ---------------------------------------------------------------------------
# Live operator: node-aware drain and failure (stub trainers, no JAX)
# ---------------------------------------------------------------------------

class _StubTrainer:
    def __init__(self, total_steps):
        self.total_steps = total_steps
        self.step_idx = 0
        self.devices_history = []
        self.closed = False

    @property
    def done(self):
        return self.step_idx >= self.total_steps

    def step(self):
        self.step_idx += 1

    def rescale(self, devices):
        from repro.core.elastic import RescaleTimings
        self.devices_history.append(tuple(devices))
        return RescaleTimings()

    def close(self):
        self.closed = True


def _controller(**kw):
    kw.setdefault("slots", 8)
    kw.setdefault("slots_per_node", 4)
    kw.setdefault("policy", PolicyConfig(rescale_gap=0.0))
    return ElasticClusterController(list(range(8)), **kw)


def test_operator_releases_trainer_on_completion():
    # one slot, two jobs: the second starts only once the first completes,
    # and by then the first must hold no trainer (its device state is freed)
    made = []

    def factory(devices):
        made.append(_StubTrainer(3))
        return made[-1]

    op = _controller(slots=1, slots_per_node=None)
    op.submit(JobSpec("a", 1, 1, 1, 0.0), factory)
    op.submit(JobSpec("b", 1, 1, 1, 0.0), factory)
    op.run()
    assert [j.status for j in op.cluster.jobs.values()] == \
        [JobStatus.COMPLETED] * 2
    assert all(live.trainer is None for live in op.live.values())
    assert [t.closed for t in made] == [True, True]
    assert [t.step_idx for t in made] == [3, 3]


def test_operator_partitions_devices_into_nodes():
    op = _controller()
    assert op.cluster.nodes() == ["base00", "base01"]


def test_operator_drain_node_migrates_live_job():
    op = _controller()
    op.submit(JobSpec("a", 1, 4, 4, 0.0, divides=8),
              lambda devices: _StubTrainer(100))
    op._process_submissions()
    job = op.cluster.jobs["a"]
    (home,) = [n for n in op.cluster.nodes() if op.cluster.residents(n)]
    other = [n for n in op.cluster.nodes() if n != home][0]
    trainer = op.live["a"].trainer
    op.drain_node(home)
    assert op.cluster.residents(home) == {}
    assert op.cluster.residents(other) == {"a": 4}
    assert job.replicas == 4                      # migrated, not shrunk
    assert len(trainer.devices_history) == 1      # live rescale onto new devs
    assert set(job.device_ids) == set(op.cluster.slots_of("a"))


def test_operator_drain_node_shrinks_when_short_on_space():
    op = _controller()
    op.submit(JobSpec("a", 1, 2, 8, 0.0, divides=8),
              lambda devices: _StubTrainer(100))
    op._process_submissions()
    job = op.cluster.jobs["a"]
    assert job.replicas == 8                      # filled both nodes
    op.drain_node("base01")
    assert job.replicas == 4                      # nowhere to migrate: shrink
    assert op.cluster.residents("base01") == {}
    assert op.cluster.jobs["a"].status is JobStatus.RUNNING


def test_operator_node_failure_restarts_only_residents():
    op = _controller()
    op.submit(JobSpec("a", 1, 4, 4, 0.0, divides=8),
              lambda devices: _StubTrainer(100))
    op.submit(JobSpec("b", 1, 4, 4, 0.0, divides=8),
              lambda devices: _StubTrainer(100))
    op._process_submissions()
    homes = {jid: [n for n in op.cluster.nodes()
                   if jid in op.cluster.residents(n)][0]
             for jid in ("a", "b")}
    assert homes["a"] != homes["b"]
    victims = op.inject_node_failure(homes["a"])
    assert victims == ["a"]
    assert op.live["a"].failures == 1
    assert op.live["b"].failures == 0
    assert op.cluster.jobs["b"].status is JobStatus.RUNNING
    # the failed node is offline: the restarted job must land elsewhere —
    # but b owns the other node, so `a` stays pending until recovery
    op._process_submissions()
    assert "a" not in op.cluster.jobs or \
        op.cluster.jobs["a"].status is not JobStatus.RUNNING
    op.recover_node(homes["a"])
    op._process_submissions()
    assert op.cluster.jobs["a"].status is JobStatus.RUNNING
    assert op.cluster.residents(homes["a"]) == {"a": 4}
