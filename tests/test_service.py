"""CACS service integration: lifecycle, periodic checkpoints, both failure
recovery paths, suspend/resume, straggler handling, termination cleanup."""
import time

import pytest

from repro.ckpt import InMemoryStore
from repro.clusters import OpenStackBackend, SnoozeBackend
from repro.core import (ASR, CACSService, CheckpointPolicy, CoordState,
                        SimulatedApp)
from tests.conftest import run_subprocess  # noqa: F401  (shared helper)


@pytest.fixture
def snooze_svc():
    backend = SnoozeBackend(n_hosts=16)
    svc = CACSService({"snooze": backend}, {"default": InMemoryStore()})
    yield svc, backend
    svc.shutdown()


@pytest.fixture
def ostack_svc():
    backend = OpenStackBackend(n_hosts=16)
    svc = CACSService({"openstack": backend}, {"default": InMemoryStore()})
    yield svc, backend
    svc.shutdown()


def _submit(svc, backend_name, n_vms=4, period=0.15, **app_kw):
    asr = ASR(name="app", n_vms=n_vms, backend=backend_name,
              app_factory=lambda: SimulatedApp(iter_time_s=0.5, state_mb=0.05,
                                               **app_kw),
              policy=CheckpointPolicy(period_s=period, keep_last=3))
    cid = svc.submit(asr)
    svc.wait_for_state(cid, CoordState.RUNNING, timeout=30)
    return cid


def _wait_recovered(svc, cid, n, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        c = svc.db.get(cid)
        if c.recoveries >= n and c.state == CoordState.RUNNING:
            return c
        time.sleep(0.02)
    raise TimeoutError(f"no recovery #{n}; state={svc.db.get(cid).state}")


def test_lifecycle_and_periodic_checkpoints(snooze_svc):
    svc, _ = snooze_svc
    cid = _submit(svc, "snooze")
    time.sleep(0.7)
    cks = svc.list_checkpoints(cid)
    assert len(cks) >= 2, "periodic checkpoints missing"
    assert len(cks) <= 3, "gc keep_last=3 violated"
    info = svc.get_checkpoint(cid, cks[-1])
    assert info["bytes"] > 0 and info["leaves"] >= 2
    final = svc.delete_coordinator(cid)
    assert final["state"] == "TERMINATED"
    # §5.4: all references removed
    assert not svc.ckpt.store().list(f"apps/{cid}")
    assert all(c["id"] != cid for c in svc.list_coordinators())


def test_vm_failure_native_notifications(snooze_svc):
    svc, backend = snooze_svc
    cid = _submit(svc, "snooze")
    time.sleep(0.4)
    coord = svc.db.get(cid)
    backend.sim.fail_host(coord.vms[1].host.host_id)
    c = _wait_recovered(svc, cid, 1)
    assert c.app.restarts == 1
    assert all(vm.reachable for vm in c.vms), "failed VM not replaced"
    assert svc.apps.monitor.native_notifications >= 1


def test_vm_failure_polling_path(ostack_svc):
    svc, backend = ostack_svc
    cid = _submit(svc, "openstack")
    time.sleep(0.4)
    coord = svc.db.get(cid)
    backend.sim.fail_host(coord.vms[0].host.host_id)
    c = _wait_recovered(svc, cid, 1)
    assert c.app.restarts == 1
    assert svc.apps.monitor.native_notifications == 0  # agent-based only


def test_app_failure_restarts_in_place(snooze_svc):
    svc, _ = snooze_svc
    cid = _submit(svc, "snooze")
    time.sleep(0.4)
    coord = svc.db.get(cid)
    vms_before = [vm.vm_id for vm in coord.vms]
    coord.app.poison()
    c = _wait_recovered(svc, cid, 1)
    # paper §6.3 case 2: same VMs, app restarted from image
    assert [vm.vm_id for vm in c.vms] == vms_before
    assert c.app.restarts == 1
    assert c.app.iteration > 0        # restored from checkpoint, not zero


def test_recovery_event_after_terminate_is_dropped(snooze_svc):
    """A monitor event for a coordinator that a terminate has removed (a
    poll in flight when it was unwatched) is dropped, not counted as a
    failed operation."""
    from repro.obs.telemetry import registry
    svc, _ = snooze_svc
    cid = _submit(svc, "snooze")
    svc.delete_coordinator(cid)
    before = registry().value("appmgr.op_errors", 0.0)
    svc.apps._submit_once(cid, svc.apps._recover, cid,
                          "vm_failure").result(timeout=10)
    assert registry().value("appmgr.op_errors", 0.0) == before


def test_recovery_restores_latest_state(snooze_svc):
    svc, backend = snooze_svc
    cid = _submit(svc, "snooze")
    time.sleep(0.6)
    coord = svc.db.get(cid)
    it_at_ckpt = coord.app.checkpoint_state()["iteration"]
    backend.sim.fail_host(coord.vms[0].host.host_id)
    c = _wait_recovered(svc, cid, 1)
    time.sleep(0.2)
    assert c.app.iteration >= max(1, it_at_ckpt - 50)


def test_suspend_resume_preserves_progress(snooze_svc):
    svc, backend = snooze_svc
    cid = _submit(svc, "snooze")
    time.sleep(0.4)
    it_before = svc.db.get(cid).app.iteration
    svc.apps.suspend(cid)
    c = svc.db.get(cid)
    assert c.state == CoordState.SUSPENDED and not c.vms
    idle_during = len(backend.sim.idle_hosts())
    svc.apps.resume(cid)
    c = svc.db.get(cid)
    assert c.state == CoordState.RUNNING
    time.sleep(0.3)
    assert c.app.iteration >= it_before   # no lost progress
    assert len(backend.sim.idle_hosts()) == idle_during - 4


def test_straggler_triggers_proactive_suspend(snooze_svc):
    svc, backend = snooze_svc
    cid = _submit(svc, "snooze", n_vms=8)
    time.sleep(0.3)
    coord = svc.db.get(cid)
    backend.sim.degrade_host(coord.vms[0].host.host_id, slowdown=100.0)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        if svc.db.get(cid).state == CoordState.SUSPENDED:
            break
        time.sleep(0.02)
    assert svc.db.get(cid).state == CoordState.SUSPENDED
    # the image exists, so the scheduler can resume it elsewhere
    assert svc.list_checkpoints(cid)


def test_service_restart_rehydrates_and_resumes():
    """§6.4 restartability end-to-end: a service instance dies (no clean
    shutdown); a fresh instance over the same stores rehydrates the
    coordinator record via CoordinatorDB.load and — after the caller
    re-attaches an app factory — restarts the job from its images."""
    from repro.ckpt import InMemoryStore as _Store
    ckpt_store, db_store = _Store(), _Store()
    svc1 = CACSService({"snooze": SnoozeBackend(n_hosts=8)},
                       {"default": ckpt_store}, db_store=db_store)
    asr = ASR(name="app", n_vms=2, backend="snooze",
              app_factory=lambda: SimulatedApp(iter_time_s=0.5,
                                               state_mb=0.05),
              policy=CheckpointPolicy(period_s=0, keep_last=3))
    cid = svc1.submit(asr)
    svc1.wait_for_state(cid, CoordState.RUNNING, timeout=30)
    time.sleep(0.2)
    step = svc1.trigger_checkpoint(cid)
    it_saved = svc1.ckpt.load(svc1.db.get(cid), step)["iteration"]
    # simulate a service-instance crash: daemons stop, no terminate — the
    # record stays in the db store and the images in the ckpt store
    svc1.apps.stop_daemons()

    svc2 = CACSService({"snooze": SnoozeBackend(n_hosts=8)},
                       {"default": ckpt_store}, db_store=db_store)
    try:
        coord = svc2.db.get(cid)              # rehydrated on start
        assert coord.state == CoordState.RUNNING   # last persisted state
        assert coord.vms == [] and coord.app is None
        assert svc2.list_checkpoints(cid) == [step]
        coord.asr.app_factory = lambda: SimulatedApp(iter_time_s=0.5,
                                                     state_mb=0.05)
        svc2.restart_from(cid, step)
        c = svc2.wait_for_state(cid, CoordState.RUNNING, timeout=30)
        assert c.app.iteration >= it_saved    # resumed from the image
        assert len(c.vms) == 2
    finally:
        svc2.shutdown()
        svc1.provision.close()


def test_restart_from_earlier_image(snooze_svc):
    svc, _ = snooze_svc
    cid = _submit(svc, "snooze", period=0.0)
    time.sleep(0.2)
    s1 = svc.trigger_checkpoint(cid)
    time.sleep(0.4)
    s2 = svc.trigger_checkpoint(cid)
    it_s2 = svc.db.get(cid).app.iteration
    info1 = svc.get_checkpoint(cid, s1)
    svc.restart_from(cid, s1)          # user picks an EARLIER image
    c = svc.db.get(cid)
    assert c.state == CoordState.RUNNING
    assert c.app.checkpoint_state()["iteration"] <= max(it_s2, 1)
    assert info1["step"] == s1
