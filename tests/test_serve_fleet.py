"""Checkpoint-backed serving fleet: scale-out by CAS restore with prefix
adoption (zero re-uploads), scale-in by suspend (capacity reclaimed for
batch), deterministic routing, chaos suspend-mid-decode, and the
request-storm DES engine."""
import dataclasses
import threading
import time

import numpy as np
import pytest

from repro.ckpt import FaultyStore, InMemoryStore
from repro.ckpt.reader import list_steps
from repro.clusters import OpenStackBackend, SnoozeBackend
from repro.configs import get_config, reduced
from repro.core import (ASR, CACSService, CheckpointPolicy, CoordState,
                        GlobalScheduler, ImageReplicator, ReplicationPolicy,
                        StandbyTarget)
from repro.obs.telemetry import registry
from repro.serve import FleetController, FleetPolicy, RequestTrace, Router
from repro.serve.engine import ServeApp
from repro.sim import active_clock
from repro.sim.serve import PARKED, ServeFleetEngine

CFG = dataclasses.replace(reduced(get_config("repro-100m")), dtype="float32")


@pytest.fixture(autouse=True)
def _virtual_time(sim_clock):
    """Whole suite on the discrete-event virtual clock."""
    yield


def _wait(pred, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        active_clock().sleep(0.02)
    return False


# ---------------------------------------------------------------------------
# workload primitives
# ---------------------------------------------------------------------------

def test_router_least_outstanding_deterministic():
    r = Router()
    for name in ("r2", "r0", "r1"):
        r.add(name)
    picks = [r.route() for _ in range(6)]
    # least outstanding, lexicographic tie-break: round-robins in order
    assert picks == ["r0", "r1", "r2", "r0", "r1", "r2"]
    r.complete("r1")
    assert r.route() == "r1"               # only r1 has 1 outstanding
    r.remove("r2")
    assert r.outstanding("r2") == 0
    assert r.route() in ("r0", "r1")
    assert r.route() is not None
    r2 = Router()
    assert r2.route() is None              # no members: rejected
    assert r2.rejected == 1


def test_request_trace_deterministic_and_restartable():
    trace = RequestTrace(seed=13, horizon_s=600.0, base_qps=2.0,
                         peak_qps=10.0, period_s=300.0,
                         burst_every_s=200.0, burst_s=20.0, burst_mult=3.0)
    a = list(trace)
    b = list(trace)                         # each iter() restarts the stream
    assert a == b
    assert len(a) > 0
    assert all(0.0 <= t <= 600.0 for t in a)
    assert a == sorted(a)
    other = list(RequestTrace(seed=14, horizon_s=600.0, base_qps=2.0,
                              peak_qps=10.0, period_s=300.0))
    assert a != other


def test_load_max_priority_caps_batch_priorities():
    from repro.sim.engine import SimEngine
    eng = SimEngine(8, seed=3)
    eng.load(n_jobs=50, horizon_s=100.0, max_priority=5)
    assert all(1 <= j.priority <= 5 for j in eng.jobs)
    with pytest.raises(ValueError):
        eng.load(n_jobs=1, horizon_s=1.0, max_priority=0)
    with pytest.raises(ValueError):
        eng.load(n_jobs=1, horizon_s=1.0, max_priority=10)


# ---------------------------------------------------------------------------
# FleetController on the real stack
# ---------------------------------------------------------------------------

def _fleet_env(n_hosts=4, n_tokens=24):
    backend = SnoozeBackend(n_hosts=n_hosts)
    store = InMemoryStore()
    svc = CACSService({"snooze": backend}, {"default": store})
    sched = GlobalScheduler(svc)            # no start(): synchronous ticks
    svc.attach_scheduler(sched)
    fleet = FleetController(
        svc, sched, name="m1",
        replica_factory=lambda: ServeApp(CFG, batch=1, prompt_len=8,
                                         n_tokens=n_tokens, cache_len=48),
        policy=FleetPolicy(min_replicas=1, max_replicas=4,
                           scale_in_idle_s=0.0),
        backend="snooze", priority=5)
    return svc, sched, fleet, store


def _publish_seed(fleet, n_seed_tokens=6):
    seed_app = ServeApp(CFG, batch=1, prompt_len=8, n_tokens=n_seed_tokens,
                        cache_len=48)
    seed_app.start(None, None)
    assert _wait(seed_app.is_done)
    seed_app.stop()
    state = seed_app.checkpoint_state()
    fleet.publish_seed(state, step=state["generated"])
    return state


def test_fleet_scale_out_adopts_seed_with_zero_reuploads():
    """Tentpole: replicas cold-start by restoring the shared seed image
    straight from CAS — nothing is uploaded, the replica's own prefix
    stays empty, and cold-start latency lands in the registry under the
    job's trace_id."""
    svc, sched, fleet, store = _fleet_env()
    try:
        seed = _publish_seed(fleet, n_seed_tokens=6)
        put_before = store.put_count
        cids = fleet.scale_out(2)
        assert len(cids) == 2
        fleet.wait_live(cids, timeout=60)
        assert fleet.coldstart_reuploads == 0
        assert store.put_count == put_before, \
            "cold start must not write a single object"
        for cid in cids:
            coord = svc.db.get(cid)
            assert coord.state == CoordState.RUNNING
            assert list_steps(store, coord.ckpt_prefix) == []
            # restored, not re-run: continues from the seed's generation
            assert coord.app.restarts == 1
            assert coord.app.generated >= seed["generated"]
            # cold start is a first-class metric under the job's trace_id
            assert coord.metrics["coldstart_s"] >= 0.0
            gauge = registry().value(f"coord.{coord.trace_id}.coldstart_s",
                                     None)
            assert gauge is not None and gauge >= 0.0
        # the generated stream extends the seed's bit-for-bit
        for cid in cids:
            coord = svc.db.get(cid)
            assert _wait(coord.app.is_done)
            out = coord.app.checkpoint_state()["tokens_out"]
            np.testing.assert_array_equal(
                out[:, :seed["tokens_out"].shape[1]], seed["tokens_out"])
        assert sorted(fleet.live()) == sorted(cids)
        assert fleet.stats()["coldstarts"] == 2
    finally:
        sched.stop()
        svc.shutdown()


def test_fleet_scale_in_parks_reclaims_capacity_then_unparks():
    """Scale-in suspends an idle replica and flags it fleet_parked: the
    scheduler hands its host to waiting batch work instead of
    auto-resuming it; a later scale-out unparks it (preempting the batch
    job right back when the cloud is full)."""
    svc, sched, fleet, store = _fleet_env(n_hosts=4, n_tokens=400)
    try:
        _publish_seed(fleet)
        cids = fleet.scale_out(2)
        fleet.wait_live(cids, timeout=60)

        from repro.core import SimulatedApp
        batch = sched.submit(ASR(
            name="batch", n_vms=3, backend="snooze", priority=1,
            app_factory=lambda: SimulatedApp(iter_time_s=0.5, state_mb=0.01),
            policy=CheckpointPolicy(period_s=0)))
        assert svc.db.get(batch).state == CoordState.QUEUED   # 2 hosts free

        parked = fleet.scale_in(1, force=True)
        assert len(parked) == 1
        coord = svc.db.get(parked[0])
        assert coord.state == CoordState.SUSPENDED
        assert coord.metrics["fleet_parked"] == 1
        assert parked[0] in fleet.parked()

        # the freed host + the 2 idle ones now fit the batch job — and the
        # parked replica must NOT be auto-resumed by the pass
        sched.tick()
        svc.wait_for_state(batch, CoordState.RUNNING, 30)
        assert svc.db.get(parked[0]).state == CoordState.SUSPENDED

        # scale-out prefers the parked replica; the cloud is full, so the
        # higher-priority replica preempts the batch job to come back
        out = fleet.scale_out(1)
        assert out == parked
        fleet.wait_live(out, timeout=60)
        assert svc.db.get(parked[0]).state == CoordState.RUNNING
        assert svc.db.get(batch).state == CoordState.SUSPENDED
        assert fleet.parks == 1 and fleet.unparks == 1
        assert registry().value("fleet.m1.parks", 0.0) == 1
    finally:
        sched.stop()
        svc.shutdown()


# ---------------------------------------------------------------------------
# chaos: suspend mid-decode, resume on another cloud, bit-identical stream
# ---------------------------------------------------------------------------

def test_suspend_mid_decode_cross_cloud_stream_bit_identical():
    """Satellite: the suspend lands while a decode holds the donated
    cache (the capture pins and waits the window out); the swap-out image
    survives a torn replication attempt (FaultyStore chaos), the job
    resumes on the *other* cloud reading only replicated chunks, and the
    final token stream is bit-identical to an unsuspended run."""
    n_tokens = 16

    ref = ServeApp(CFG, batch=1, prompt_len=8, n_tokens=n_tokens,
                   cache_len=48)
    ref.start(None, None)
    assert _wait(ref.is_done)
    ref.stop()
    ref_tokens = ref.checkpoint_state()["tokens_out"]

    gate_entered = threading.Event()
    gate_release = threading.Event()
    made = []

    class _Gated(ServeApp):
        """Parks the dispatch of the step that would take the slot past 5
        tokens until released; once the swap-out has pinned, holds the
        next dispatch until the suspend stops the app, so the suspend
        lands mid-decode however long its write takes. The resumed
        incarnation (``restarts`` 1) decodes freely."""
        pinned = False

        def _capture(self):
            snap = super()._capture()
            self.pinned = True
            return snap

        def _build(self):
            super()._build()
            real = self.engine.decode

            def decode(cache, token, pos):
                if self._slot_count() >= 5 and not gate_release.is_set():
                    gate_entered.set()
                    gate_release.wait(30)
                if self.pinned and not self.restarts:
                    self._stop.wait(30)
                return real(cache, token, pos)
            self.engine.decode = decode

    def factory():
        # only the first incarnation is gated: the resumed app (restored
        # past the gate) must decode freely
        app = _Gated(CFG, batch=1, prompt_len=8, n_tokens=n_tokens,
                     cache_len=48) if not made else \
            ServeApp(CFG, batch=1, prompt_len=8, n_tokens=n_tokens,
                     cache_len=48)
        made.append(app)
        return app

    store_a = InMemoryStore()
    inner_b = InMemoryStore()
    store_b = FaultyStore(inner_b)
    svc = CACSService({"snooze": SnoozeBackend(4),
                       "openstack": OpenStackBackend(4)},
                      {"default": store_a, "standby": store_b})
    try:
        cid = svc.submit(ASR(name="serve", n_vms=1, backend="snooze",
                             app_factory=factory,
                             policy=CheckpointPolicy(period_s=0)))
        svc.wait_for_state(cid, CoordState.RUNNING, 60)
        assert gate_entered.wait(30), "decode never reached the gate"

        # suspend now: the donated cache is surrendered to the gated
        # decode, so the capture must pin and wait — not deadlock, not
        # poll virtual time
        err = []

        def do_suspend():
            try:
                svc.apps.suspend(cid, reason="chaos")
            except Exception as e:             # noqa: BLE001
                err.append(e)
        t = threading.Thread(target=do_suspend, daemon=True)
        t.start()
        time.sleep(0.3)
        assert t.is_alive(), "suspend finished inside the donated window"
        gate_release.set()
        t.join(timeout=60)
        assert not t.is_alive() and not err
        coord = svc.db.get(cid)
        assert coord.state == CoordState.SUSPENDED
        gen_at_suspend = made[0].generated
        assert 5 <= gen_at_suspend < n_tokens

        # replicate the swap-out image to the standby cloud — first
        # attempt torn by chaos (invisible: no COMMITTED), retry heals
        rep = ImageReplicator(svc)
        rep.add_target(StandbyTarget("standby", store=store_b,
                                     backend="openstack"))
        rep.watch(cid, ReplicationPolicy(targets=("standby",)))
        store_b.arm_put_errors(1)
        rep.sync()
        assert rep.sync_errors >= 1
        assert list_steps(store_b, coord.ckpt_prefix) == []
        store_b.disarm()
        rep.sync()
        assert len(list_steps(store_b, coord.ckpt_prefix)) == 1

        # retarget home to the standby cloud and resume there: the
        # restore reads only replicated chunks — zero uploads to B
        svc.ckpt.detach(cid)
        coord.asr.backend = "openstack"
        coord.asr.policy.store = "standby"
        puts_before = inner_b.put_count
        svc.apps.resume(cid, block=True)
        assert coord.state == CoordState.RUNNING
        assert inner_b.put_count == puts_before

        app = made[-1]
        assert app.restarts == 1
        assert _wait(app.is_done)
        out = app.checkpoint_state()["tokens_out"]
        np.testing.assert_array_equal(out, ref_tokens)
    finally:
        gate_release.set()
        svc.shutdown()


# ---------------------------------------------------------------------------
# request-storm DES engine
# ---------------------------------------------------------------------------

def _des(seed=11, policy=None, **kw):
    trace = RequestTrace(seed=seed, horizon_s=7200.0, base_qps=4.0,
                         peak_qps=35.0, period_s=3600.0,
                         burst_every_s=600.0, burst_s=120.0, burst_mult=3.0)
    pol = policy or FleetPolicy(min_replicas=1, max_replicas=8,
                                target_util=0.7, scale_in_idle_s=30.0,
                                eval_period_s=5.0)
    eng = ServeFleetEngine(16, seed, trace=trace, policy=pol,
                           service_s=0.1, concurrency=2,
                           replica_boot_s=5.0, suspend_s=2.0, **kw)
    eng.start_fleet(pol.min_replicas)
    eng.load(n_jobs=30, horizon_s=7200.0, max_vms=4, mean_work_s=600.0,
             max_priority=8)
    return eng


def test_serve_fleet_engine_deterministic_trace():
    a, b = _des(), _des()
    a.run()
    b.run()
    assert a.trace_digest() == b.trace_digest()
    assert a.served == b.served == a.requests
    assert a.fleet_stats() == b.fleet_stats()
    assert a.requests > 50_000              # a storm, not a trickle
    assert a.parks > 0 and a.coldstarts > 1 # the autoscaler actually moved
    a.check_invariants()
    for jid in a.parked_jids:
        assert a.jobs[jid].state == PARKED


def test_serve_fleet_engine_survives_host_faults():
    eng = _des(seed=5, host_mtbf_s=3000.0)
    eng.run()
    eng.check_invariants()
    assert eng.served == eng.requests
    assert eng.recoveries > 0
    e2 = _des(seed=5, host_mtbf_s=3000.0)
    e2.run()
    assert eng.trace_digest() == e2.trace_digest()


def test_pooled_fleet_beats_static_on_diurnal_storm():
    """The benchmark's claim, in miniature: under a diurnal+bursty storm
    an autoscaled (pooled) fleet yields BOTH better p99 (it scales to the
    peak) and better served-QPS-per-host-second (it parks the trough)
    than a static mid-sized fleet, on identical request bytes."""
    pooled_pol = FleetPolicy(min_replicas=1, max_replicas=8,
                             target_util=0.7, scale_in_idle_s=30.0,
                             eval_period_s=5.0)
    static_pol = FleetPolicy(min_replicas=4, max_replicas=4,
                             target_util=0.7, scale_in_idle_s=1e18,
                             eval_period_s=5.0)
    pooled = _des(seed=21, policy=pooled_pol)
    static = _des(seed=21, policy=static_pol)
    pooled.run()
    static.run()
    ps, ss = pooled.fleet_stats(), static.fleet_stats()
    assert ps["requests"] == ss["requests"]          # identical storm
    assert ps["p99_s"] < ss["p99_s"]
    assert ps["served_qps_per_host"] > ss["served_qps_per_host"]
