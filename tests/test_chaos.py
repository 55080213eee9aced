"""Chaos tests: multi-device serving under injected device faults.

Runs under the project-standard 8 forced-host devices (conftest).  The
contract under chaos extends the exec/ correctness contract: a fatal
device fault mid-run loses NO requests — they fail over to healthy
replicas and resolve bit-identical to serial execution; the victim
replica walks quarantine → probation → recovery (or ejection after
repeated probe failures); and everything joins in bounded time.

Determinism note: which replica serves first on a 1-core host is thread-
wakeup order, so device-targeted fault schedules first DISCOVER the
serving device (one probe request) and then arm the rule at it.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_jni_tpu import exec as xc
from spark_rapids_jni_tpu import types as T
from spark_rapids_jni_tpu.column import Column, DictColumn, Table
from spark_rapids_jni_tpu.exec.placement import Replica, device_name
from spark_rapids_jni_tpu.faultinj import injector as finj
from spark_rapids_jni_tpu.faultinj.resilience import DeviceQuarantined
from spark_rapids_jni_tpu.utils import flight, metrics


@pytest.fixture(autouse=True)
def _chaos_env():
    metrics.set_enabled(True)
    metrics.reset()
    flight.reset()
    yield
    finj.get_injector().disable()
    metrics.reset()
    metrics.set_enabled(None)


def _mktab(n, seed):
    rng = np.random.default_rng(seed)
    return Table([Column(T.DType(T.TypeId.INT32),
                         jnp.asarray(rng.integers(0, 100, n, dtype=np.int32))),
                  Column(T.DType(T.TypeId.INT32),
                         jnp.asarray(rng.integers(0, 7, n, dtype=np.int32)))])


def _q_sum(tbls):
    t = tbls["t"]
    return Table([Column(T.DType(T.TypeId.INT64),
                         jnp.sum(t.columns[0].data.astype(jnp.int64))
                         .reshape(1))])


def _canon(result):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(result)]


def _same(a, b):
    return len(a) == len(b) and all(
        x.shape == y.shape and np.array_equal(x, y) for x, y in zip(a, b))


def _incident_kinds():
    return {e["kind"] for e in flight.events()
            if e["kind"].startswith("incident:")}


def _wait_replica(sched, index, pred, timeout=15.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        snap = sched.ops_state()["replicas"][index]
        if pred(snap):
            return snap
        time.sleep(0.02)
    return sched.ops_state()["replicas"][index]


# --- the headline chaos run --------------------------------------------------


def test_fatal_fault_mid_run_failover_bit_identical():
    """One-shot fatal fault on the serving device mid-run: every request
    still resolves, bit-identical to serial; the victim quarantines,
    requests fail over, and the recovery probe re-admits it."""
    assert len(jax.devices()) >= 4
    tables = {"t": _mktab(4096, 0)}
    oracle = _canon(_q_sum(tables))
    inj = finj.get_injector()
    t_start = time.monotonic()
    with xc.QueryScheduler(workers=4, devices=4, probe_base_s=0.02,
                           probe_max_s=0.2) as sched:
        # one-shot untargeted kill: whichever replica serves the next
        # request faults fatally (which one is thread-wakeup order; the
        # victim is discovered afterwards from replica state)
        inj.load_dict({"seed": 1, "sites": {
            "exec.dispatch": {"percent": 100,
                              "injectionType": "device_error",
                              "maxHits": 1}}})
        inj.enable()
        tickets = [sched.submit("q", _q_sum, tables) for _ in range(16)]
        for tk in tickets:
            assert _same(_canon(tk.result(timeout=120)), oracle), \
                "request lost or corrupted under chaos"
        # the fault fired exactly once and took exactly one replica down
        assert inj.injected_count == 1
        vi = next(i for i, r in enumerate(sched.replicas)
                  if r.resilient.fatal_count >= 1)
        snap = _wait_replica(
            sched, vi,
            lambda s: s["state"] == "healthy" and s["recoveries"] >= 1)
        assert snap["state"] == "healthy", snap
        assert snap["fatal_faults"] == 1 and snap["recoveries"] == 1, snap
        # at least one request relocated off the victim, and relocated
        # requests record their failover hop on the ticket
        relocated = [tk for tk in tickets if tk.relocations > 0]
        assert relocated, "no request failed over"
        counters = metrics.snapshot()["counters"]
        assert counters.get("exec.failover.relocated", 0) >= 1
        assert counters.get("exec.failover.recovered", 0) >= 1
        kinds = _incident_kinds()
        assert {"incident:quarantine", "incident:failover",
                "incident:recovery"} <= kinds, kinds
    # bounded-time join: chaos must not wedge shutdown
    assert time.monotonic() - t_start < 90


def test_multi_device_routing_spreads_load():
    """Independent slow requests spread across replicas (least-loaded is
    emergent: busy workers don't pull), and per-device completion
    counters account for every response."""
    tables = {"t": _mktab(512, 1)}

    def slow(tbls):
        time.sleep(0.03)
        return _q_sum(tbls)

    with xc.QueryScheduler(workers=4, devices=4, coalesce_ms=0) as sched:
        tickets = [sched.submit("slow", slow, tables, compiled=False)
                   for _ in range(16)]
        for tk in tickets:
            tk.result(timeout=120)
        used = {tk.device for tk in tickets}
        assert len(used) >= 2, f"all requests pinned to {used}"
        counters = metrics.snapshot()["counters"]
        per_dev = {r.name: counters.get(
            "exec.device." + r.name.replace(":", "") + ".completed", 0)
            for r in sched.replicas}
        assert sum(per_dev.values()) == 16, per_dev


def test_ejection_after_repeated_probe_failures():
    """A persistently-faulting device fails its recovery canaries and is
    permanently ejected; the rest of the pool keeps serving."""
    tables = {"t": _mktab(1024, 2)}
    oracle = _canon(_q_sum(tables))
    inj = finj.get_injector()
    # probe_base large enough that the first canary fires AFTER the
    # device-targeted kill rule below is armed: the six results between
    # the fault and the re-arm include a relocated request's compile on
    # the survivor, which took over 0.5 s on a loaded host (the driver's
    # run of PR 32's first tree)
    with xc.QueryScheduler(workers=2, devices=2, probe_base_s=2.0,
                           probe_max_s=2.4, eject_after=2) as sched:
        # step 1: one-shot untargeted fault downs whichever replica
        # serves; step 2: pin an UNLIMITED rule to that device so its
        # recovery canaries keep failing until ejection
        inj.load_dict({"seed": 1, "sites": {
            "exec.dispatch": {"percent": 100,
                              "injectionType": "device_error",
                              "maxHits": 1}}})
        inj.enable()
        tickets = [sched.submit("q", _q_sum, tables) for _ in range(6)]
        for tk in tickets:
            assert _same(_canon(tk.result(timeout=120)), oracle)
        vi = next(i for i, r in enumerate(sched.replicas)
                  if r.resilient.fatal_count >= 1)
        victim = sched.replicas[vi].name
        inj.load_dict({"seed": 1, "sites": {
            "exec.dispatch": {"percent": 100,
                              "injectionType": "device_error",
                              "device": victim}}})
        snap = _wait_replica(sched, vi,
                             lambda s: s["state"] == "ejected")
        assert snap["state"] == "ejected", snap
        counters = metrics.snapshot()["counters"]
        assert counters.get("exec.failover.probe_failed", 0) >= 2
        assert counters.get("exec.failover.ejected", 0) == 1
        assert "incident:ejected" in _incident_kinds()
        # the survivor still serves after the ejection
        inj.disable()
        tk = sched.submit("q", _q_sum, tables)
        assert _same(_canon(tk.result(timeout=60)), oracle)
        assert tk.device != victim


def test_whole_pool_quarantined_fails_fast_and_drains():
    """recovery=False pins the legacy terminal-quarantine contract at
    pool scope: once every replica is down, queued requests drain with
    a typed error and later submits fail fast."""
    tables = {"t": _mktab(256, 3)}
    inj = finj.get_injector()
    inj.load_dict({"seed": 1, "sites": {
        "exec.dispatch": {"percent": 100,
                          "injectionType": "device_error"}}})
    inj.enable()
    with xc.QueryScheduler(workers=2, devices=2, recovery=False,
                           coalesce_ms=0) as sched:
        tickets = [sched.submit("q", _q_sum, tables) for _ in range(8)]
        failures = 0
        for tk in tickets:
            with pytest.raises(DeviceQuarantined):
                tk.result(timeout=60)
            failures += 1
        assert failures == 8            # drained, not wedged
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            try:
                sched.submit("after", _q_sum, tables)
            except DeviceQuarantined:
                break
            time.sleep(0.01)
        else:
            pytest.fail("pool-wide quarantine did not fail fast")


# --- placement ---------------------------------------------------------------


def test_placement_replicates_and_caches():
    """Replica.place moves every buffer to its device, bit-identical,
    preserving DictColumn structure (codes + dictionary, no
    materialization), and identity-caches repeat placements."""
    devs = jax.devices()
    assert len(devs) >= 4
    rep = Replica(3, devs[3])
    chars = np.frombuffer(b"aabbbcc", dtype=np.uint8)
    dcol = Column(T.string, jnp.asarray(chars),
                  jnp.asarray([0, 2, 5, 7], jnp.int32))
    codes = jnp.asarray([2, 0, 1, 1, 0], jnp.int32)
    tab = Table([Column(T.DType(T.TypeId.INT32),
                        jnp.arange(5, dtype=jnp.int32)),
                 DictColumn(codes, dcol, sorted_dict=True)])
    placed = rep.place({"t": tab})["t"]
    assert isinstance(placed.columns[1], DictColumn), \
        "placement materialized the dict column"
    assert placed.columns[1].sorted_dict
    for arr in (placed.columns[0].data, placed.columns[1].codes,
                placed.columns[1].dictionary.data):
        assert arr.devices() == {devs[3]}, arr.devices()
    np.testing.assert_array_equal(np.asarray(placed.columns[0].data),
                                  np.arange(5, dtype=np.int32))
    np.testing.assert_array_equal(np.asarray(placed.columns[1].codes),
                                  np.asarray(codes))
    # identity cache: placing the same source buffers again reuses the
    # same device copies (stable plan-cache fingerprints per device)
    placed2 = rep.place({"t": tab})["t"]
    assert placed2.columns[0].data is placed.columns[0].data
    assert placed2.columns[1].codes is placed.columns[1].codes
    counters = metrics.snapshot()["counters"]
    assert counters.get("exec.place.hit", 0) >= 1
    assert counters.get("exec.place.copy", 0) >= 1


def test_placement_scope_sets_device_identity():
    devs = jax.devices()
    rep = Replica(2, devs[2])
    assert rep.name == device_name(devs[2])
    with rep.scope():
        assert finj.current_device() == rep.name
    assert finj.current_device() is None


# --- prefetch slot hygiene under failures ------------------------------------


def test_prefetch_slot_discarded_on_queue_deadline():
    """A loader-backed request that dies at its queue deadline must free
    its staged slot (exec.prefetch.discarded) instead of pinning
    double-buffer capacity forever."""
    tables = {"t": _mktab(256, 4)}

    def blocker_q(tbls):
        time.sleep(0.3)
        return _q_sum(tbls)

    with xc.QueryScheduler(workers=1, devices=1, coalesce_ms=0) as sched:
        blocker = sched.submit("blocker", blocker_q, tables,
                               compiled=False)
        doomed = sched.submit("doomed", _q_sum,
                              loader=lambda: tables, timeout_s=0.01,
                              compiled=False)
        with pytest.raises(xc.ExecDeadlineExceeded):
            doomed.result(timeout=60)
        blocker.result(timeout=60)
        counters = metrics.snapshot()["counters"]
        assert counters.get("exec.prefetch.discarded", 0) >= 1, counters
