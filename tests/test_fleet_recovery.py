"""Crash-safe fleet replanning: write-ahead journal, snapshot/restore, and
the supervised controller/worker split.

The acceptance contract: a controller killed at ANY tick of a seeded chaos
trace and restored from its journal finishes the trace with a
``fleet_digest()`` bit-identical to an uninterrupted run and zero invalid
published ticks.  Plus the unit surface underneath it — CRC'd record codec,
torn-tail recovery, snapshot cadence/compaction, supervisor retry/restart
semantics, and poison-problem quarantine.
"""

import time

import pytest

import repro.fleet.service as svc_mod
from repro.fleet import (ChaosSpec, InlineWorker, Journal, JournalError,
                         PodCountChange, ReplanService, SimulatedCrash,
                         StageDrift, SubprocessWorker, Supervisor,
                         ThreadWorker, TransportChaos, WorkerFailed,
                         WorkerTimeout, crash_restart_run, event_from_wire,
                         event_to_wire, gen_burst_trace, inject_chaos,
                         make_fleet, subprocess_supervisor)
from repro.fleet.journal import decode_record, encode_record


def _small_fleet(seed=11):
    pairs, groups = make_fleet(3, 3, n=8, p=4, seed=seed)
    trace = gen_burst_trace(groups, 10, seed=seed + 1, n_stages=8,
                            initial_pods=4, burst_prob=0.7)
    return pairs, inject_chaos(trace, groups, ChaosSpec(), seed=seed + 2)


def _journal(tmp_path, **kw):
    kw.setdefault("fsync", False)   # tmpfs + tests: skip the disk barrier
    return Journal(tmp_path / "journal", **kw)


# ---------------------------------------------------------------------------
# Record codec + WAL torn-tail recovery
# ---------------------------------------------------------------------------

def test_record_codec_round_trip():
    payload = {"tick": 3, "events": [["StageDrift",
                                      {"instance": 1, "stage": 2,
                                       "factor": 1.5}]]}
    assert decode_record(encode_record(payload)) == payload


@pytest.mark.parametrize("mangle", [
    lambda b: b[: len(b) // 2],                 # torn mid-record
    lambda b: b"deadbeef" + b[8:],              # CRC mismatch
    lambda b: b[:9] + b"not json\n",            # unparseable payload
    lambda b: b"xx\n",                          # too short to hold a CRC
])
def test_corrupt_records_are_detected(mangle):
    good = encode_record({"tick": 0, "events": []})
    with pytest.raises(JournalError):
        decode_record(mangle(good))


def test_wal_recovers_longest_good_prefix(tmp_path):
    j = _journal(tmp_path)
    for t in range(4):
        j.append(t, [StageDrift(0, 1, 2.0)])
    j.close()
    # Simulate a crash mid-append: tear the final record in half.
    data = j.wal_path.read_bytes()
    j.wal_path.write_bytes(data[: len(data) - 10])
    records, error = j.read_wal()
    assert [r["tick"] for r in records] == [0, 1, 2]
    assert error is not None and "record 3" in error
    with pytest.raises(JournalError):
        j.read_wal(strict=True)


def test_wal_survives_mid_log_corruption_to_prefix(tmp_path):
    j = _journal(tmp_path)
    for t in range(3):
        j.append(t, [])
    j.close()
    lines = j.wal_path.read_bytes().splitlines(keepends=True)
    lines[1] = b"00000000 {}\n"   # CRC of b"{}" is not 0: detected
    j.wal_path.write_bytes(b"".join(lines))
    records, error = j.read_wal()
    assert [r["tick"] for r in records] == [0]
    assert "record 1" in error


def test_event_wire_codec_round_trips_all_types():
    from repro.fleet import PodFailure, StageTimings
    events = [StageTimings(3, (0.5, 1.25, 2.0)), StageDrift(1, 4, 3.0),
              PodCountChange(2, 6), PodFailure(0, 1)]
    for ev in events:
        assert event_from_wire(event_to_wire(ev)) == ev
    with pytest.raises(ValueError):
        event_from_wire(["NoSuchEvent", {}])


# ---------------------------------------------------------------------------
# Snapshot cadence, compaction, restore
# ---------------------------------------------------------------------------

def test_snapshot_compacts_wal_and_prunes_old_snapshots(tmp_path):
    pairs, trace = _small_fleet()
    j = _journal(tmp_path, snapshot_every=4, keep_snapshots=2)
    svc = ReplanService(pairs, journal=j)
    svc.run_trace(trace)
    records, error = j.read_wal()
    assert error is None
    # WAL holds only the ticks the oldest RETAINED snapshot hasn't absorbed
    # (kept that far back so restore can fall back past a corrupt newest).
    snaps = j._snapshot_paths()
    assert len(snaps) <= 2
    oldest_tick = snaps[0][0]
    assert all(r["tick"] >= oldest_tick for r in records)
    assert len(records) <= j.snapshot_every * j.keep_snapshots


def test_restore_at_genesis_without_any_ticks(tmp_path):
    pairs, _ = _small_fleet()
    j = _journal(tmp_path)
    svc = ReplanService(pairs, journal=j)
    restored = ReplanService.restore(j)
    assert restored.tick_count == 0
    assert restored.fleet_digest() == svc.fleet_digest()


def test_restore_reproduces_state_and_continues_identically(tmp_path):
    pairs, trace = _small_fleet()
    ref = ReplanService(pairs)
    ref.run_trace(trace)

    j = _journal(tmp_path, snapshot_every=3)
    svc = ReplanService(pairs, journal=j)
    for events in trace.ticks[:6]:
        svc.tick(events)
    svc.journal.close()

    restored = ReplanService.restore(j)
    assert restored.tick_count == 6
    assert restored.fleet_digest() == svc.fleet_digest()
    restored.resume_trace(trace)
    assert restored.fleet_digest() == ref.fleet_digest()
    assert restored.metrics.invalid_published == 0
    # Count-based metrics survive the snapshot + replay round trip exactly.
    for field in ("ticks", "requests", "solves", "warm_hits", "events",
                  "deferred", "fallback_solves", "dropped_events"):
        assert getattr(restored.metrics, field) == getattr(ref.metrics, field)


def test_restore_skips_corrupt_snapshot_in_favor_of_older(tmp_path):
    pairs, trace = _small_fleet()
    j = _journal(tmp_path, snapshot_every=3, keep_snapshots=3)
    svc = ReplanService(pairs, journal=j)
    for events in trace.ticks[:7]:
        svc.tick(events)
    svc.journal.close()
    snaps = sorted((tmp_path / "journal").glob("snapshot_*.json"))
    assert len(snaps) >= 2
    snaps[-1].write_bytes(b"00000000 torn\n")   # newest snapshot corrupted
    # Compaction keeps the WAL back to the oldest retained snapshot, so
    # recovery falls back to the older snapshot and replays forward to the
    # exact same state.
    restored = ReplanService.restore(j)
    assert restored.tick_count == svc.tick_count
    assert restored.fleet_digest() == svc.fleet_digest()


def test_restore_without_snapshot_raises(tmp_path):
    with pytest.raises(JournalError):
        ReplanService.restore(_journal(tmp_path))


def test_journaling_is_observation_only(tmp_path):
    """A journaled run publishes bit-identical plans to an unjournaled one."""
    pairs, trace = _small_fleet()
    plain = ReplanService(pairs)
    plain.run_trace(trace)
    journaled = ReplanService(pairs, journal=_journal(tmp_path))
    journaled.run_trace(trace)
    assert journaled.fleet_digest() == plain.fleet_digest()


# ---------------------------------------------------------------------------
# The tentpole property: crash anywhere, recover bit-identically
# ---------------------------------------------------------------------------

def test_crash_at_every_tick_recovers_bit_identically(tmp_path):
    """For EVERY tick of the seeded chaos trace: kill the controller
    mid-tick (events journaled, state untouched), restore from the journal,
    finish the trace — digest matches the uninterrupted run, zero invalid
    published ticks, and metrics agree tick-for-tick."""
    pairs, trace = _small_fleet()
    ref = ReplanService(pairs)
    ref.run_trace(trace)
    for crash_tick in range(trace.num_ticks):
        d = tmp_path / f"crash_{crash_tick}"
        svc, restarts = crash_restart_run(
            pairs, trace, Journal(d, snapshot_every=4, fsync=False),
            crash_ticks=[crash_tick])
        assert len(restarts) == 1
        assert svc.fleet_digest() == ref.fleet_digest(), \
            f"digest diverged after crash at tick {crash_tick}"
        assert svc.metrics.ticks == ref.metrics.ticks
        assert svc.metrics.invalid_published == 0


def test_double_crash_including_crash_during_catchup(tmp_path):
    pairs, trace = _small_fleet()
    ref = ReplanService(pairs)
    ref.run_trace(trace)
    svc, restarts = crash_restart_run(
        pairs, trace, Journal(tmp_path / "j", snapshot_every=4, fsync=False),
        crash_ticks=[3, 4])   # second kill lands right after the first restore
    assert len(restarts) == 2
    assert svc.fleet_digest() == ref.fleet_digest()


def test_crash_with_torn_wal_tail_still_recovers(tmp_path):
    """Crash plus a half-written final record (the real kill -9 shape): the
    torn record's tick is re-fetched from the trace by resume_trace, so the
    outcome is still bit-identical."""
    pairs, trace = _small_fleet()
    ref = ReplanService(pairs)
    ref.run_trace(trace)
    j = Journal(tmp_path / "j", snapshot_every=4, fsync=False)
    svc = ReplanService(pairs, journal=j)
    for events in trace.ticks[:6]:
        svc.tick(events)
    svc.journal.close()
    data = j.wal_path.read_bytes()
    j.wal_path.write_bytes(data[: len(data) - 7])   # tear tick 5's record
    restored = ReplanService.restore(j)
    assert restored.tick_count == 5   # recovered to the last good record
    restored.resume_trace(trace)
    assert restored.fleet_digest() == ref.fleet_digest()


def test_simulated_crash_fires_before_state_mutation(tmp_path):
    pairs, trace = _small_fleet()
    j = Journal(tmp_path / "j", fsync=False)
    svc = ReplanService(pairs, journal=j)
    digest_before = svc.fleet_digest()

    def hook(tick):
        raise SimulatedCrash("boom")

    svc.crash_hook = hook
    with pytest.raises(SimulatedCrash):
        svc.tick(trace.ticks[0])
    assert svc.fleet_digest() == digest_before
    assert svc.tick_count == 0
    records, _ = j.read_wal()
    assert [r["tick"] for r in records] == [0]   # WAL wrote ahead of the crash


# ---------------------------------------------------------------------------
# Supervisor: retries, backoff, worker restarts, timeouts
# ---------------------------------------------------------------------------

def test_supervisor_retries_with_exponential_backoff_then_raises():
    calls, delays = [], []

    def flaky(batch):
        calls.append(batch)
        raise RuntimeError("transient")

    sup = Supervisor(flaky, max_attempts=4, backoff_base=0.01,
                     backoff_max=0.03, sleep=delays.append)
    with pytest.raises(WorkerFailed):
        sup.solve("pb")
    assert len(calls) == 4
    assert delays == [0.01, 0.02, 0.03]   # doubles, then clamps
    assert sup.stats.retries == 3 and sup.stats.failures == 4


def test_supervisor_recovers_when_a_retry_succeeds():
    attempts = []

    def flaky(batch):
        attempts.append(1)
        if len(attempts) < 2:
            raise RuntimeError("first attempt dies")
        return ["ok"]

    sup = Supervisor(flaky, max_attempts=3, backoff_base=0, sleep=lambda s: None)
    assert sup.solve("pb") == ["ok"]
    assert sup.stats.retries == 1 and sup.stats.dispatches == 2


def test_thread_worker_timeout_restarts_worker():
    import time as _time

    def hang(batch):
        _time.sleep(0.5)
        return ["late"]

    sup = Supervisor(hang, worker_cls=ThreadWorker, max_attempts=2,
                     timeout=0.05, backoff_base=0, sleep=lambda s: None)
    first_worker = sup.pool[0]
    with pytest.raises(WorkerFailed) as ei:
        sup.solve("pb")
    assert isinstance(ei.value.__cause__, WorkerTimeout)
    assert sup.stats.restarts >= 1
    assert sup.pool[0] is not first_worker
    sup.close()


def test_inline_worker_is_transparent():
    w = InlineWorker(lambda b: [b, b])
    assert w.solve("x") == ["x", "x"]
    assert w.solves == 1 and w.alive(0.0)


def test_service_results_identical_under_thread_workers():
    pairs, trace = _small_fleet()
    ref = ReplanService(pairs)
    ref.run_trace(trace)
    svc = ReplanService(pairs)
    svc.supervisor = Supervisor(svc._solve_group, worker_cls=ThreadWorker,
                                workers=2, timeout=30.0)
    svc.run_trace(trace)
    assert svc.fleet_digest() == ref.fleet_digest()
    svc.supervisor.close()


def test_supervisor_timeout_with_inline_worker_is_rejected():
    """Deadline protection over a synchronous worker is fictional — the
    misconfiguration must fail at construction, not silently no-op."""
    import functools
    with pytest.raises(ValueError, match="preempt"):
        Supervisor(lambda b: b, timeout=1.0)
    with pytest.raises(ValueError, match="preempt"):
        Supervisor(lambda b: b, timeout=1.0,
                   worker_cls=functools.partial(InlineWorker))
    # No timeout, or a preemptable transport: fine.
    Supervisor(lambda b: b)
    Supervisor(lambda b: b, worker_cls=ThreadWorker, timeout=1.0).close()


def test_supervisor_counts_timeouts_separately_from_failures():
    def hang(batch):
        time.sleep(0.5)
        return ["late"]

    sup = Supervisor(hang, worker_cls=ThreadWorker, max_attempts=2,
                     timeout=0.05, backoff_base=0, sleep=lambda s: None)
    with pytest.raises(WorkerFailed):
        sup.solve("pb")
    assert sup.stats.timeouts == 2 and sup.stats.failures == 0
    # Abandoned (unkillable) threads are surfaced, not silently leaked.
    sup.close()
    assert sup.stats.leaked_threads == 2


def test_thread_worker_close_cancels_queued_work():
    ran = []

    def slow(batch):
        time.sleep(0.3)
        ran.append(batch)
        return [batch]

    w = ThreadWorker(slow)
    first = w._ex.submit(w._run, "running")
    queued = w._ex.submit(w._run, "queued")
    w.close()   # shutdown(cancel_futures=True): queued work must NOT run
    assert queued.cancelled()
    first.result(timeout=5)
    assert ran == ["running"]


# ---------------------------------------------------------------------------
# SubprocessWorker: real process isolation, kill-based preemption
# ---------------------------------------------------------------------------

def _batch(seed=0, rows=3, n=8, p=4):
    import numpy as np
    from repro.core.batched import ProblemBatch
    rng = np.random.default_rng(seed)
    w = rng.uniform(0.5, 2.0, size=(rows, n))
    delta = rng.uniform(0.1, 1.0, size=(rows, n + 1))
    s = np.sort(rng.uniform(0.5, 2.0, size=(rows, p)))[:, ::-1].copy()
    return ProblemBatch.from_arrays(w, delta, s, 10.0)


def _inline_reference(pb):
    from repro.core.batched import batched_min_period
    return batched_min_period(pb, "numpy")


@pytest.mark.parametrize("backend", ["jax", "pallas", "fused", "sharded"])
def test_device_backend_pool_of_two_is_refused(backend):
    """A chip belongs to one process: two workers on a device backend would
    race for it, so construction refuses before any child is spawned."""
    with pytest.raises(ValueError, match="workers=1"):
        subprocess_supervisor(backend=backend, workers=2)


@pytest.mark.slow
def test_subprocess_worker_is_bit_identical_to_inline():
    pb = _batch(seed=21)
    sup = subprocess_supervisor(workers=1, timeout=60.0)
    try:
        assert sup.solve(pb) == _inline_reference(pb)
    finally:
        sup.close()


@pytest.mark.slow
@pytest.mark.parametrize("fault,expect_timeout", [
    ({"kill_prob": 1.0}, False),        # SIGKILL after the request is sent
    ({"doa_prob": 1.0}, False),         # dead before the first heartbeat
    ({"corrupt_prob": 1.0}, False),     # reply frame fails CRC -> poisoned
    ({"truncate_prob": 1.0}, None),     # stalled/desynced reply
])
def test_subprocess_fault_matrix_recovers_with_one_restart(fault,
                                                           expect_timeout):
    """Each injected wire fault costs exactly one worker restart and the
    retried solve still matches the inline run bit-for-bit."""
    pb = _batch(seed=22)
    chaos = TransportChaos(max_faults=1, seed=13, **fault)
    sup = subprocess_supervisor(workers=1, timeout=2.0, chaos=chaos,
                                max_attempts=3, backoff_base=0.0,
                                term_grace=0.2)
    try:
        assert sup.solve(pb) == _inline_reference(pb)
        assert chaos.total_faults() == 1
        assert sup.stats.restarts == 1
        if expect_timeout is True:
            assert sup.stats.timeouts >= 1
        elif expect_timeout is False:
            assert sup.stats.failures >= 1
    finally:
        sup.close()


@pytest.mark.slow
def test_wedged_solve_is_reaped_by_sigkill_within_timeout():
    """The preemption guarantee: a wedged worker that IGNORES SIGTERM is
    killed by the kernel within timeout + term_grace, and the hang is
    accounted as a timeout (not a failure)."""
    pb = _batch(seed=23)
    chaos = TransportChaos(wedge_prob=1.0, wedge_seconds=30.0, max_faults=1,
                           seed=5)
    timeout, grace = 0.75, 0.2
    sup = subprocess_supervisor(workers=1, timeout=timeout, chaos=chaos,
                                max_attempts=1, term_grace=grace,
                                ignore_sigterm=True)
    wedged = sup.pool[0]
    t0 = time.perf_counter()
    with pytest.raises(WorkerFailed) as ei:
        sup.solve(pb)
    wall = time.perf_counter() - t0
    sup.close()
    assert isinstance(ei.value.__cause__, WorkerTimeout)
    assert wall < timeout + grace + 2.0   # reaped, not waited out (30s wedge)
    assert wedged._proc.returncode == -9  # SIGTERM ignored -> SIGKILL won
    assert wedged.sigkills == 1
    assert sup.stats.timeouts == 1 and sup.stats.failures == 0
    assert sup.stats.sigkills == 1


@pytest.mark.slow
def test_dead_worker_detected_by_alive_and_replaced():
    sup = subprocess_supervisor(workers=1, timeout=60.0)
    try:
        victim = sup.pool[0]
        victim._proc.kill()
        victim._proc.wait()
        assert not victim.alive(None)
        pb = _batch(seed=24)
        assert sup.solve(pb) == _inline_reference(pb)   # replaced pre-dispatch
        assert sup.stats.restarts == 1
        assert sup.pool[0] is not victim
    finally:
        sup.close()


@pytest.mark.slow
def test_service_digest_identical_under_subprocess_workers_with_kills():
    """The tentpole contract at service level: repeated SIGKILLs mid-solve
    leave the published fleet state bit-identical to the inline run, with
    zero invalid published ticks and every restart attributable to an
    injected fault."""
    pairs, trace = _small_fleet()
    ref = ReplanService(pairs)
    ref.run_trace(trace)

    chaos = TransportChaos(kill_prob=0.5, max_faults=4, seed=1)
    svc = ReplanService(pairs)
    svc.supervisor = subprocess_supervisor(workers=2, timeout=60.0,
                                           chaos=chaos, max_attempts=3,
                                           backoff_base=0.0)
    svc._sync_acct_baselines()
    svc.run_trace(trace)
    svc.supervisor.close()

    assert svc.fleet_digest() == ref.fleet_digest()
    assert svc.metrics.invalid_published == 0
    assert chaos.counts.get("kill", 0) >= 1          # chaos actually fired
    assert 1 <= svc.metrics.worker_restarts <= chaos.total_faults()


# ---------------------------------------------------------------------------
# Poison quarantine
# ---------------------------------------------------------------------------

def test_poison_problem_is_quarantined_after_double_failures(monkeypatch):
    pairs, _ = _small_fleet()
    svc = ReplanService(pairs, quarantine_after=2)
    svc.supervisor.sleep = lambda s: None
    healthy_digest = svc.fleet_digest()

    def boom(*a, **k):
        raise RuntimeError("poisoned solve")

    monkeypatch.setattr(svc_mod, "batched_min_period", boom)
    monkeypatch.setattr(svc_mod, "min_period_exhaustive", boom)

    # Strike 1: batched AND scalar fail; the request defers (retry next tick).
    svc.tick([StageDrift(0, 0, 2.0)])
    assert svc.quarantine_strikes and not svc.quarantined
    assert svc._pending
    # Strike 2 (the deferred retry): quarantined, request pinned to the last
    # valid plan and NOT re-pended.
    svc.tick([])
    assert svc.quarantined and not svc._pending
    assert svc.metrics.quarantined_problems == 1
    assert svc.metrics.quarantined_requests >= 1
    # Quarantined ticks never solve, never wedge, never publish invalid.
    svc.tick([])
    assert svc.fleet_digest() == healthy_digest   # kept the last valid plans
    assert svc.metrics.invalid_published == 0
    assert not svc._pending

    # Drift that changes the signature re-enters the solve path: with the
    # solver healed, the instance replans out of quarantine.
    monkeypatch.undo()
    svc.tick([PodCountChange(0, 3)])
    assert svc.metrics.invalid_published == 0
    assert svc.states[0].plan.mapping.alloc is not None
    assert not svc._pending


def test_quarantine_state_survives_restore(tmp_path, monkeypatch):
    pairs, _ = _small_fleet()
    j = _journal(tmp_path, snapshot_every=1)
    svc = ReplanService(pairs, journal=j, quarantine_after=1)
    svc.supervisor.sleep = lambda s: None

    def boom(*a, **k):
        raise RuntimeError("poisoned solve")

    monkeypatch.setattr(svc_mod, "batched_min_period", boom)
    monkeypatch.setattr(svc_mod, "min_period_exhaustive", boom)
    svc.tick([StageDrift(0, 0, 2.0)])
    monkeypatch.undo()
    assert svc.quarantined
    svc.journal.close()
    # snapshot_every=1 put a post-tick snapshot on disk, so restore comes up
    # from state alone (no WAL replay) — the quarantine bookkeeping must
    # round-trip through the snapshot, not be re-derived by re-failing.
    restored = ReplanService.restore(j)
    assert restored.tick_count == 1 and restored.replayed_ticks == 0
    assert restored.quarantined == svc.quarantined
    assert restored.quarantine_strikes == svc.quarantine_strikes
    assert restored.metrics.quarantined_problems == 1
