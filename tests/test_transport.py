"""Out-of-process transport: framing, loopback parity, failure paths.

The remote backend's correctness contract is bit-exactness against the
in-process oracle: every decoded block from ``connect(spec,
backend="remote")`` must equal the local backend's output — across
schemes, both supported primes, and survivor masks — because the workers
run the SAME staged jit programs on plan tables they rebuild
deterministically (DESIGN.md §13).  The failure-path tests drive the
worker chaos hooks (scripted death/stall) and assert the degradation
contract: phase-2 loss → ``engine.fail`` → retune/replan → re-dispatch,
phase-3 loss → absorbed by the survivor mask, stalled socket → deadline
→ evict → same replan path, all without hanging the flush.
"""
import json
import os
import socket
import threading

import numpy as np
import pytest

from repro.mpc import Field, MPCSpec, P_DEFAULT, P_MERSENNE31, connect
from repro.mpc.byzantine import FaultInjector
from repro.mpc.protocol import AGECMPCProtocol
from repro.transport import TransportClosed, recv_msg, send_msg
from repro.transport.framing import MAX_HEADER_BYTES


def exact_matmul(a, b, p):
    return np.array((a.astype(object) @ b.astype(object)) % p, np.int64)


def _remote_pair(spec, **opts):
    """A (local, remote) session pair over one spec."""
    return connect(spec), connect(spec, backend="remote", **opts)


# ================================================================ framing
class TestFraming:
    def _pair(self):
        return socket.socketpair()

    def test_meta_and_arrays_round_trip(self):
        ours, theirs = self._pair()
        arrs = {"g": np.arange(12, dtype=np.int64).reshape(3, 4),
                "i": np.array([[2**62, 0], [1, -5]], dtype=np.int64)}
        send_msg(ours, {"kind": "x", "block": 7}, arrs)
        meta, got = recv_msg(theirs, timeout=5.0)
        assert meta["kind"] == "x" and meta["block"] == 7
        assert sorted(got) == ["g", "i"]
        for k in arrs:
            assert got[k].dtype == np.int64
            np.testing.assert_array_equal(got[k], arrs[k])
        ours.close(), theirs.close()

    def test_empty_payload_frame(self):
        ours, theirs = self._pair()
        send_msg(ours, {"kind": "stop"})
        meta, got = recv_msg(theirs, timeout=5.0)
        assert meta == {"kind": "stop"} and got == {}
        ours.close(), theirs.close()

    def test_many_frames_stay_ordered(self):
        ours, theirs = self._pair()
        for i in range(20):
            send_msg(ours, {"n": i}, {"a": np.full((2, 2), i, np.int64)})
        for i in range(20):
            meta, got = recv_msg(theirs, timeout=5.0)
            assert meta["n"] == i and int(got["a"][0, 0]) == i
        ours.close(), theirs.close()

    def test_oversized_header_refused_at_send(self):
        from repro.mpc.errors import InvariantError

        ours, theirs = self._pair()
        with pytest.raises(InvariantError, match="header"):
            send_msg(ours, {"pad": "x" * (MAX_HEADER_BYTES + 1)})
        ours.close(), theirs.close()

    def test_recv_timeout_propagates(self):
        ours, theirs = self._pair()
        with pytest.raises(socket.timeout):
            recv_msg(theirs, timeout=0.05)
        ours.close(), theirs.close()

    def test_peer_close_raises_transport_closed(self):
        ours, theirs = self._pair()
        ours.close()
        with pytest.raises(TransportClosed):
            recv_msg(theirs, timeout=5.0)
        theirs.close()

    def test_jax_arrays_ride_the_same_wire(self):
        import jax.numpy as jnp

        ours, theirs = self._pair()
        send_msg(ours, {"kind": "x"}, {"a": jnp.arange(6).reshape(2, 3)})
        _, got = recv_msg(theirs, timeout=5.0)
        np.testing.assert_array_equal(got["a"],
                                      np.arange(6).reshape(2, 3))
        ours.close(), theirs.close()


# ====================================================== loopback parity
@pytest.mark.parametrize("scheme", ["age", "entangled", "polydot"])
@pytest.mark.parametrize("p", [P_DEFAULT, P_MERSENNE31])
def test_remote_bit_identical_to_local(scheme, p):
    """The acceptance sweep: loopback remote decode == in-process decode,
    bit for bit, across schemes × primes."""
    spec = MPCSpec(s=2, t=2, z=1, scheme=scheme, field=Field(p))
    loc, rem = _remote_pair(spec)
    rng = np.random.default_rng(hash((scheme, p)) % 2**31)
    a = rng.integers(0, p, (5, 7))
    b = rng.integers(0, p, (7, 4))
    y_loc = np.asarray(loc.matmul(a, b, encoded=True))
    y_rem = np.asarray(rem.matmul(a, b, encoded=True))
    np.testing.assert_array_equal(y_rem, y_loc)
    np.testing.assert_array_equal(y_rem, exact_matmul(a, b, p))
    rem.backend.close()


@pytest.mark.parametrize("drop", [0, 2])
def test_remote_bit_identical_under_survivor_masks(drop):
    spec = MPCSpec(s=2, t=2, z=1)
    n, p = spec.n_workers, spec.field.p
    mask = np.ones(n, bool)
    mask[drop] = False
    loc, rem = _remote_pair(spec)
    rng = np.random.default_rng(drop)
    a = rng.integers(0, p, (6, 6))
    b = rng.integers(0, p, (6, 6))
    y_loc = np.asarray(loc.matmul(a, b, encoded=True, survivors=mask))
    y_rem = np.asarray(rem.matmul(a, b, encoded=True, survivors=mask))
    np.testing.assert_array_equal(y_rem, y_loc)
    rem.backend.close()


def test_remote_pipelined_multi_block_parity():
    """Several in-flight blocks through the double-buffered window decode
    identically to serial local serving (fixed-point path)."""
    spec = MPCSpec(s=2, t=2, z=1)
    loc, rem = _remote_pair(spec)
    rng = np.random.default_rng(11)
    pairs = [(rng.standard_normal((8, 8)), rng.standard_normal((8, 8)))
             for _ in range(4)]
    for a, b in pairs:
        np.testing.assert_array_equal(np.asarray(rem.matmul(a, b)),
                                      np.asarray(loc.matmul(a, b)))
    assert rem.backend.stats["blocks"] >= 4
    rem.backend.close()


def test_remote_barriered_mode_matches_pipelined():
    spec = MPCSpec(s=2, t=2, z=1)
    rng = np.random.default_rng(12)
    a = rng.integers(0, spec.field.p, (6, 6))
    b = rng.integers(0, spec.field.p, (6, 6))
    rem_p = connect(spec, backend="remote", pipelined=True)
    rem_b = connect(spec, backend="remote", pipelined=False)
    np.testing.assert_array_equal(
        np.asarray(rem_p.matmul(a, b, encoded=True)),
        np.asarray(rem_b.matmul(a, b, encoded=True)))
    rem_p.backend.close(), rem_b.backend.close()


def test_remote_rejects_byzantine_specs_at_connect():
    spec = MPCSpec(s=2, t=2, z=2, adversaries=1)
    with pytest.raises(ValueError, match="remote backend does not verify"):
        connect(spec, backend="remote")
    with pytest.raises(ValueError, match="remote backend does not verify"):
        connect(MPCSpec(s=2, t=2, z=2), backend="remote",
                injector=FaultInjector(seed=1, rate=1.0))


# ====================================================== failure recovery
class TestKillMidFlush:
    """Chaos-scripted deaths mid-flush degrade into the elastic path."""

    def _spec(self):
        return MPCSpec(s=2, t=2, z=1)

    def test_phase2_death_replans_and_recovers(self):
        """A worker dying BEFORE its G row lands is a phase-2 loss: no I
        point is complete without it, so the backend must fail the
        device, replan, and re-dispatch — and still decode correctly."""
        spec = self._spec()
        loc, rem = _remote_pair(spec)
        proto = AGECMPCProtocol.from_spec(spec, m=6)
        rem.backend.chaos(proto, 1, die_block=0, die_after="shares")
        rng = np.random.default_rng(21)
        a = rng.integers(0, spec.field.p, (6, 6))
        b = rng.integers(0, spec.field.p, (6, 6))
        y = np.asarray(rem.matmul(a, b, encoded=True, m=6))
        np.testing.assert_array_equal(y, exact_matmul(a, b, spec.field.p))
        assert rem.backend.stats["phase_losses"] >= 1
        assert rem.backend.stats["redispatches"] >= 1
        rem.backend.close()

    def test_phase3_death_absorbed_by_mask(self):
        """A worker dying AFTER its G row is a phase-3 loss: only its own
        I-point echo is missing, and any t²+z survivors decode — free."""
        spec = self._spec()
        loc, rem = _remote_pair(spec)
        proto = AGECMPCProtocol.from_spec(spec, m=6)
        rem.backend.chaos(proto, 2, die_block=0, die_after="ipoint")
        rng = np.random.default_rng(22)
        a = rng.integers(0, spec.field.p, (6, 6))
        b = rng.integers(0, spec.field.p, (6, 6))
        y = np.asarray(rem.matmul(a, b, encoded=True, m=6))
        np.testing.assert_array_equal(y, exact_matmul(a, b, spec.field.p))
        assert rem.backend.stats["phase3_absorbed"] >= 1
        assert rem.backend.stats["phase_losses"] == 0
        rem.backend.close()

    def test_timeout_evicts_and_replans_deterministically(self):
        """A stalled socket must NOT hang the flush: the deadline fires,
        the worker is evicted, and the block re-dispatches through the
        same replan path — with a bit-identical result on a re-run."""
        spec = self._spec()
        rng = np.random.default_rng(23)
        a = rng.integers(0, spec.field.p, (6, 6))
        b = rng.integers(0, spec.field.p, (6, 6))

        def run_once():
            rem = connect(spec, backend="remote", deadline_s=0.5,
                          retries=0)
            proto = AGECMPCProtocol.from_spec(spec, m=6)
            rem.backend.chaos(proto, 0, stall_block=0, stall_s=30.0)
            y = np.asarray(rem.matmul(a, b, encoded=True, m=6))
            stats = dict(rem.backend.stats)
            rem.backend.close()
            return y, stats

        y1, st1 = run_once()
        y2, st2 = run_once()
        np.testing.assert_array_equal(y1, y2)
        np.testing.assert_array_equal(y1,
                                      exact_matmul(a, b, spec.field.p))
        for st in (st1, st2):
            assert st["evictions"] >= 1
            assert st["phase_losses"] >= 1

    def test_retry_resends_before_evicting(self):
        """A short stall inside the retry budget is absorbed by a resend
        (idempotent worker replies), with no eviction."""
        spec = self._spec()
        rem = connect(spec, backend="remote", deadline_s=0.4, retries=2)
        proto = AGECMPCProtocol.from_spec(spec, m=6)
        rem.backend.chaos(proto, 0, stall_block=0, stall_s=0.8)
        rng = np.random.default_rng(24)
        a = rng.integers(0, spec.field.p, (6, 6))
        b = rng.integers(0, spec.field.p, (6, 6))
        y = np.asarray(rem.matmul(a, b, encoded=True, m=6))
        np.testing.assert_array_equal(y, exact_matmul(a, b, spec.field.p))
        assert rem.backend.stats["retries"] >= 1
        assert rem.backend.stats["evictions"] == 0
        rem.backend.close()


# ============================================== shared fault schedules
class TestFaultScheduleFile:
    """One JSON schedule file, two consumers: the transport chaos hooks
    and the fleet simulator's FleetEvent replay (DESIGN.md §9/§11)."""

    def test_injector_json_round_trip(self, tmp_path):
        inj = FaultInjector(seed=5,
                            schedule={0: [(1, "tamper")],
                                      3: [(0, "flip"), (2, "stale")]},
                            rate=0.5, slots=(0, 2), mode="flip")
        path = tmp_path / "faults.json"
        inj.save(str(path))
        back = FaultInjector.load(str(path))
        assert back.to_json() == inj.to_json()
        assert back.schedule == {0: [(1, "tamper")],
                                 3: [(0, "flip"), (2, "stale")]}
        assert back.seed == 5 and back.rate == 0.5
        assert back.slots == (0, 2) and back.mode == "flip"
        # runtime state (the corruption log) is not configuration
        assert back.log == []

    def test_unsupported_version_rejected(self):
        with pytest.raises(ValueError, match="version"):
            FaultInjector.from_json({"version": 99, "schedule": []})

    def test_empty_schedule_normalizes_to_none(self):
        back = FaultInjector.from_json(FaultInjector(seed=1).to_json())
        assert back.schedule is None

    def test_to_fleet_events_projection(self):
        inj = FaultInjector(schedule={2: [(4, "tamper")], 0: [(1, "tag")]})
        ev = inj.to_fleet_events(round_us=100.0)
        assert [(e.at_us, e.device, e.kind) for e in ev] == [
            (0.0, 1, "corrupt"), (200.0, 4, "corrupt")]

    def test_one_file_drives_transport_chaos_and_replay(self, tmp_path):
        """The same saved schedule kills transport workers (as erasure
        chaos) AND projects onto fleet-sim corruption events."""
        spec = MPCSpec(s=2, t=2, z=1)
        inj = FaultInjector(schedule={0: [(1, "tamper")]})
        path = tmp_path / "shared.json"
        inj.save(str(path))
        shared = FaultInjector.load(str(path))
        # consumer 1: the fleet-sim replay view
        events = shared.to_fleet_events(round_us=50.0)
        assert [(e.device, e.kind) for e in events] == [(1, "corrupt")]
        # consumer 2: transport chaos — a liar the wire cannot verify is
        # evicted, i.e. killed at the scripted (round → block) point
        rem = connect(spec, backend="remote")
        proto = AGECMPCProtocol.from_spec(spec, m=6)
        assert shared.schedule is not None
        for rnd, entries in shared.schedule.items():
            for slot, _mode in entries:
                rem.backend.chaos(proto, slot, die_block=rnd,
                                  die_after="shares")
        rng = np.random.default_rng(31)
        a = rng.integers(0, spec.field.p, (6, 6))
        b = rng.integers(0, spec.field.p, (6, 6))
        y = np.asarray(rem.matmul(a, b, encoded=True, m=6))
        np.testing.assert_array_equal(y, exact_matmul(a, b, spec.field.p))
        assert rem.backend.stats["phase_losses"] >= 1
        rem.backend.close()


# ========================================================= phase timings
def test_recorder_collects_wire_phase_samples():
    """The driver feeds measured per-phase/per-device samples through the
    PhaseRecorder hook, in the shape sim.calibrate fits (device ids,
    klass names, positive scalar counts and µs)."""
    from repro.sim.trace import PhaseRecorder

    rec = PhaseRecorder()
    spec = MPCSpec(s=2, t=2, z=1)
    rem = connect(spec, backend="remote", recorder=rec)
    rng = np.random.default_rng(41)
    a = rng.integers(0, spec.field.p, (6, 6))
    b = rng.integers(0, spec.field.p, (6, 6))
    rem.matmul(a, b, encoded=True)
    rem.backend.close()
    phases = {s.phase for s in rec.samples}
    assert {"encode", "compute", "exchange", "decode"} <= phases
    per_dev = [s for s in rec.samples if s.phase in ("compute", "exchange")]
    n = spec.n_workers
    assert {s.device for s in per_dev} == set(range(n))
    for s in rec.samples:
        assert s.scalars > 0 and s.us >= 0.0
        assert s.klass == spec.scheme


def test_process_workers_never_see_the_accelerator(monkeypatch):
    """spawn="process" children get JAX_PLATFORMS=cpu in their starting
    environment: only the parent process may hold a chip."""
    from repro.transport import dealer

    seen = []

    def fake_popen(argv, env):
        seen.append(env)
        raise OSError("not spawning in this test")

    monkeypatch.setattr(dealer.subprocess, "Popen", fake_popen)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    proto = AGECMPCProtocol.from_spec(MPCSpec(s=2, t=2, z=1), m=4)
    with pytest.raises(OSError, match="not spawning"):
        dealer.Dealer(proto, spawn="process")
    assert seen and seen[0]["JAX_PLATFORMS"] == "cpu"


@pytest.mark.skipif(not os.environ.get("RUN_TRANSPORT_PROC"),
                    reason="process-spawn loopback is exercised by "
                           "examples/transport_demo.py (CI smoke); set "
                           "RUN_TRANSPORT_PROC=1 to run here too")
def test_remote_process_spawn_parity():
    spec = MPCSpec(s=2, t=2, z=1)
    loc, rem = _remote_pair(spec, spawn="process")
    rng = np.random.default_rng(51)
    a = rng.integers(0, spec.field.p, (6, 6))
    b = rng.integers(0, spec.field.p, (6, 6))
    np.testing.assert_array_equal(
        np.asarray(rem.matmul(a, b, encoded=True)),
        np.asarray(loc.matmul(a, b, encoded=True)))
    rem.backend.close()
