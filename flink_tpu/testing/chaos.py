"""Seeded, deterministic fault injection for the runtime's chaos tests.

Analog of the reference's jepsen harness (``flink-jepsen/src/jepsen/flink/
nemesis.clj``) folded into the library: the runtime exposes **named fault
points** — ``checkpoint.store`` / ``checkpoint.load`` (storage layer),
``channel.send`` / ``channel.recv`` (data plane), ``rpc.call`` (control
plane), ``heartbeat.deliver`` (liveness), ``subtask.run`` /
``subtask.snapshot`` (task threads), ``device.dispatch`` (accelerator
lane), ``queryable.replica_fetch`` (the serving tier's bulk checkpoint
fetch; fired with ``direction="storage->replica"`` so
``Partition(direction=)`` cuts exactly the replica's data plane),
``rescale.redistribute`` / ``rescale.redeploy`` (the rescale lifecycle's
channel-state redistribution and redeploy steps — the
:class:`KillDuringRescale` prey), ``ha.lease`` (the HA store's lease
renewal write: :class:`TruncatedWrite` tears the renewal so the
verify-back demotes the holder loudly; :class:`KillCoordinator` fails
the n-th renewal outright — the leader "dies" and a standby takes over
at epoch + 1) — each
a near-zero-cost :func:`fire` call that consults the
installed :class:`FaultInjector`.  Tests attach *schedules*
(fail-K-times-then-succeed, crash-once-at-N, delay-by-D,
partition-until-healed, seeded probabilistic failure) to points and get a
reproducible failure sequence: schedules keyed by per-point counters (and
per-point RNGs derived from the injector seed) produce identical action
histories on every run regardless of thread interleaving elsewhere.

:class:`FreezableProxy` (promoted out of ``tests/test_nemesis.py``) is the
TCP-level injector for real-socket paths — a one-link network partition
where bytes neither flow nor error while both endpoints stay up.

Usage::

    inj = FaultInjector(seed=7)
    inj.inject("checkpoint.store", FailTimes(2))
    with installed(inj):
        cluster.execute(plan)
    assert inj.history("checkpoint.store")[:2] == ["fail", "fail"]

This module imports only the standard library so every runtime layer can
call :func:`fire` without import cycles or overhead when no injector is
installed.
"""

from __future__ import annotations

import random
import socket
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence, Tuple, Union

__all__ = [
    "InjectedFault", "FaultSchedule", "FailTimes", "CrashOnceAt", "DelayBy",
    "SlowDisk", "SlowConsumer", "ActionSequence", "Partition",
    "FailWithProbability", "WedgedDevice", "ClockSkew", "KillDuringRescale",
    "KillCoordinator", "TruncatedWrite",
    "FaultInjector", "FreezableProxy", "install", "uninstall", "installed",
    "fire", "active", "blocked", "skew", "truncated",
]

#: actions a schedule may return for one firing
OK = "ok"          # proceed normally
FAIL = "fail"      # raise InjectedFault at the fault point
DROP = "drop"      # suppress delivery (heartbeats) / stall the link (channels)
HANG = "hang"      # block the firing thread until the schedule heals — the
#                    wedged-accelerator model (device_health watchdog prey)
# ("delay", seconds), ("fail", message) and ("skew", offset_ms) are the
# parameterized kinds
Action = Union[str, Tuple[str, float], Tuple[str, str]]


class InjectedFault(RuntimeError):
    """The error raised at a firing fault point (schedule said ``fail``)."""


class FaultSchedule:
    """Maps the 1-based firing count of a point to an action.

    Subclasses implement :meth:`action`; they must be pure functions of
    ``(n, rng)`` (plus their own construction parameters and explicit
    state transitions like :meth:`Partition.heal`) so the same seed yields
    the same failure sequence on every run."""

    def action(self, n: int, rng: random.Random) -> Action:
        raise NotImplementedError

    def dropping(self) -> bool:
        """Is the schedule in a PERSISTENT drop state right now?  Polled by
        stalled senders (via :func:`blocked`) without advancing the firing
        counter.  Default False: a one-shot ``drop`` from a sequence is a
        momentary loss, not a stall — only :class:`Partition` (and
        :class:`WedgedDevice`) keeps a link down until explicitly healed."""
        return False

    def matches(self, ctx: Dict) -> bool:
        """Does this schedule apply to a firing with context ``ctx``?
        Unmatched firings proceed normally WITHOUT advancing the counter,
        RNG or history (so directional schedules stay deterministic
        regardless of how much opposite-direction traffic flows).  Default:
        applies to every firing."""
        return True


class FailTimes(FaultSchedule):
    """Fail the first ``k`` firings, then succeed forever — the transient
    storage-flake model (retry/backoff must absorb exactly ``k`` errors).
    ``message`` customizes the raised error text, letting tests steer
    error CLASSIFIERS (e.g. the device-health monitor reads
    RESOURCE_EXHAUSTED as an OOM)."""

    def __init__(self, k: int, message: Optional[str] = None):
        self.k = k
        self.message = message

    def action(self, n: int, rng: random.Random) -> Action:
        if n > self.k:
            return OK
        return FAIL if self.message is None else (FAIL, self.message)


class CrashOnceAt(FaultSchedule):
    """Fail exactly the ``n``-th firing (1-based), once — crash-at-
    checkpoint-N / crash-mid-window."""

    def __init__(self, n: int):
        self.n = n

    def action(self, n: int, rng: random.Random) -> Action:
        return FAIL if n == self.n else OK


class DelayBy(FaultSchedule):
    """Delay each firing by ``seconds`` (the first ``times`` firings when
    given) — slow-disk / slow-network injection."""

    def __init__(self, seconds: float, times: Optional[int] = None):
        self.seconds = seconds
        self.times = times

    def action(self, n: int, rng: random.Random) -> Action:
        if self.times is not None and n > self.times:
            return OK
        return ("delay", self.seconds)


class SlowDisk(FaultSchedule):
    """Seeded, jittered write stalls — the degrading-disk model (writes
    intermittently take ~seconds instead of ~ms, without erroring).

    Unlike :class:`DelayBy`'s constant delay, each firing stalls with
    probability ``p`` for a duration drawn uniformly from
    ``[min_s, max_s]`` out of the point's own seeded RNG — a realistic
    bursty-latency profile that is still a pure function of
    ``(seed, point, firing count)``, so two runs with one seed stall at
    identical firings for identical durations.  ``times`` bounds the flaky
    period (the disk "recovers" afterwards)."""

    def __init__(self, max_s: float, min_s: float = 0.0, p: float = 1.0,
                 times: Optional[int] = None):
        if max_s < min_s:
            raise ValueError("SlowDisk: max_s must be >= min_s")
        self.max_s = max_s
        self.min_s = min_s
        self.p = p
        self.times = times

    def action(self, n: int, rng: random.Random) -> Action:
        # ALWAYS draw both samples: the RNG stream must advance identically
        # per firing regardless of which branch a firing takes, or later
        # firings' actions would depend on earlier probabilities
        gate = rng.random()
        span = self.min_s + (self.max_s - self.min_s) * rng.random()
        if self.times is not None and n > self.times:
            return OK
        if gate >= self.p:
            return OK
        return ("delay", span)


class SlowConsumer(FaultSchedule):
    """Seeded, BURSTY per-channel drain stalls — the slow-consumer model
    (a sink or operator that intermittently falls behind, so its input
    queues deepen and barriers crawl behind the backlog).

    Fired at the ``channel.recv`` point (one firing per element actually
    dequeued): with probability ``p`` a firing STARTS a burst of ``burst``
    consecutive stalled dequeues, each stalling for a duration drawn
    uniformly from ``[min_s, max_s]`` out of the point's seeded RNG.
    Bursts — not independent per-element stalls — are what make input
    queues deepen faster than they drain, the condition unaligned
    checkpoints exist for.  Still a pure function of (seed, point, firing
    count): both RNG samples are drawn on EVERY firing (the SlowDisk
    invariant), and the burst countdown advances only with the strictly
    ordered firing counter.  ``times`` bounds the flaky period; ``channel``
    (a substring of the channel name) scopes the schedule to matching
    channels — unmatched firings advance nothing."""

    def __init__(self, max_s: float, min_s: float = 0.0, p: float = 0.05,
                 burst: int = 8, times: Optional[int] = None,
                 channel: Optional[str] = None):
        if max_s < min_s:
            raise ValueError("SlowConsumer: max_s must be >= min_s")
        if burst < 1:
            raise ValueError("SlowConsumer: burst must be >= 1")
        self.max_s = max_s
        self.min_s = min_s
        self.p = p
        self.burst = burst
        self.times = times
        self.channel = channel
        self._burst_left = 0

    def matches(self, ctx: Dict) -> bool:
        return self.channel is None or self.channel in str(
            ctx.get("channel", ""))

    def action(self, n: int, rng: random.Random) -> Action:
        # ALWAYS draw both samples (SlowDisk invariant): the RNG stream
        # must advance identically per firing regardless of branch
        gate = rng.random()
        span = self.min_s + (self.max_s - self.min_s) * rng.random()
        if self.times is not None and n > self.times:
            self._burst_left = 0
            return OK
        if self._burst_left > 0:
            self._burst_left -= 1
            return ("delay", span)
        if gate < self.p:
            self._burst_left = self.burst - 1
            return ("delay", span)
        return OK


class TruncatedWrite(FaultSchedule):
    """Tear durable writes short: firings ``at .. at+times-1`` return a
    ``("truncate", frac)`` action — the fault point (storage consults it
    via :meth:`FaultInjector.truncated`) persists only the first
    ``frac`` of the payload's bytes, models a crash/power-cut after the
    file was published (torn page past the rename).  The CRC/size gate on
    load is expected to classify the survivor as corrupt and fall back to
    an older base."""

    def __init__(self, at: int = 1, frac: float = 0.5, times: int = 1):
        if not 0.0 <= frac < 1.0:
            raise ValueError("TruncatedWrite: frac must be in [0, 1)")
        self.at = at
        self.frac = frac
        self.times = times

    def action(self, n: int, rng: random.Random) -> Action:
        if self.at <= n < self.at + self.times:
            return ("truncate", self.frac)
        return OK


class ActionSequence(FaultSchedule):
    """Explicit per-firing script (``["ok", "fail", "fail"]``), then
    ``then`` forever — arbitrary deterministic scenarios."""

    def __init__(self, actions: Sequence[Action], then: Action = OK):
        self.actions = list(actions)
        self.then = then

    def action(self, n: int, rng: random.Random) -> Action:
        return self.actions[n - 1] if n <= len(self.actions) else self.then


class Partition(FaultSchedule):
    """Suppress delivery until healed (``drop`` while active) — the
    logical-link partition; :class:`FreezableProxy` is its TCP twin.

    ``direction`` makes the partition ASYMMETRIC: only firings whose
    context carries a matching ``direction=...`` are dropped; everything
    else (the opposite direction, or callers that pass no direction)
    proceeds without even advancing the schedule's counter.  The classic
    one-way-partition false suspect: A's messages to B blackhole while
    B→A flows.

    ``replica`` scopes the partition to ONE queryable read replica (the
    fan-out siblings fire the same point with ``replica=<name>`` context):
    only the named replica's fetches blackhole — the failover nemesis that
    proves reads continue via the siblings."""

    def __init__(self, active: bool = True,
                 direction: Optional[str] = None,
                 replica: Optional[str] = None):
        self.direction = direction
        self.replica = replica
        self._active = threading.Event()
        if active:
            self._active.set()

    def matches(self, ctx: Dict) -> bool:
        return (self.direction is None
                or ctx.get("direction") == self.direction) \
            and (self.replica is None
                 or ctx.get("replica") == self.replica)

    def partition(self) -> None:
        self._active.set()

    def heal(self) -> None:
        self._active.clear()

    @property
    def healed(self) -> bool:
        return not self._active.is_set()

    def action(self, n: int, rng: random.Random) -> Action:
        return DROP if self._active.is_set() else OK

    def dropping(self) -> bool:
        return self._active.is_set()


class WedgedDevice(FaultSchedule):
    """Hang the firing thread from the ``at``-th firing until healed — the
    wedged-accelerator model (a device client that stops answering:
    ``block_until_ready`` then blocks forever).  Deterministic: firing
    ``at`` (and
    every later one while active) parks inside :meth:`FaultInjector.fire`
    in a ``dropping()`` poll loop; :meth:`heal` releases it.  The
    device-health watchdog is expected to abandon the hung dispatch from
    outside long before then — the parked thread is the sacrifice."""

    def __init__(self, at: int = 1):
        self.at = at
        self._active = threading.Event()
        self._active.set()
        self._reached = threading.Event()   # a firing actually wedged

    def heal(self) -> None:
        self._active.clear()

    @property
    def healed(self) -> bool:
        return not self._active.is_set()

    @property
    def wedged_once(self) -> bool:
        """Did any firing actually park?  (Test synchronization hook.)"""
        return self._reached.is_set()

    def action(self, n: int, rng: random.Random) -> Action:
        if self._active.is_set() and n >= self.at:
            self._reached.set()
            return HANG
        return OK

    def dropping(self) -> bool:
        return self._active.is_set()


class ClockSkew(FaultSchedule):
    """Seeded clock skew applied per clock READING (``clock.wall`` /
    ``clock.monotonic`` points, consumed via :func:`skew`): offset =
    cumulative step ``jumps`` + linear ``drift_ms_per_read`` + seeded
    jitter in ``[-jitter_ms, +jitter_ms]``.

    ``jumps`` is a sequence of ``(reading_n, delta_ms)``: from the n-th
    reading onward the clock is additionally offset by ``delta_ms``
    (negative = backward step, positive = forward jump).  Pure function of
    (seed, point, reading count) — two runs with one seed see identical
    skewed clocks.  ``times`` bounds the skewed period (NTP "recovers"
    afterwards)."""

    def __init__(self, jumps: Sequence[Tuple[int, float]] = (),
                 drift_ms_per_read: float = 0.0, jitter_ms: float = 0.0,
                 times: Optional[int] = None):
        self.jumps = list(jumps)
        self.drift = float(drift_ms_per_read)
        self.jitter = float(jitter_ms)
        self.times = times

    def action(self, n: int, rng: random.Random) -> Action:
        # ALWAYS draw: the RNG stream must advance identically per reading
        # regardless of the recovered/skewed branch (SlowDisk invariant)
        j = (2.0 * rng.random() - 1.0) * self.jitter
        if self.times is not None and n > self.times:
            return OK
        off = sum(d for at, d in self.jumps if n >= at)
        return ("skew", off + self.drift * n + j)


class KillDuringRescale(FaultSchedule):
    """Kill (or stall, then kill) INSIDE the rescale window — fired at the
    ``rescale.redistribute`` point, which the rescale lifecycle hits after
    the pre-rescale cut is taken and before the job redeploys at the new
    parallelism.  Deterministic: the ``at``-th rescale through the point
    dies (``times`` consecutive rescales when given), everything else
    proceeds.  ``stall_s`` sleeps before the kill so partition/stall
    composites can hold the window open.  The rescale lifecycle is
    expected to absorb the kill: re-trigger the redistribution from the
    same pre-rescale checkpoint (idempotent — the cut is immutable), or
    roll back to the old parallelism past its retry budget; either way
    zero records may be lost or duplicated."""

    def __init__(self, at: int = 1, times: int = 1, stall_s: float = 0.0):
        if times < 1:
            raise ValueError("KillDuringRescale: times must be >= 1")
        self.at = at
        self.times = times
        self.stall_s = stall_s

    def action(self, n: int, rng: random.Random) -> Action:
        if self.at <= n < self.at + self.times:
            if self.stall_s > 0:
                # one composite firing: stall first (holds the rescale
                # window open), then die — FaultInjector sleeps on the
                # delay branch, so model it as a slow kill message
                time.sleep(self.stall_s)
            return (FAIL, f"killed during rescale (firing {n})")
        return OK


class KillCoordinator(FaultSchedule):
    """Kill the LEADER coordinator — fired at the ``ha.lease`` point,
    which the HA store hits on every lease renewal write.  Deterministic:
    the ``at``-th renewal (``times`` consecutive renewals when given)
    fails outright, so the :class:`~flink_tpu.runtime.ha.LeaseRenewer`
    invokes its ``on_lost`` demotion and the leader stands down exactly
    as if the process died mid-flight: the lease ages out, a standby
    acquires it at epoch + 1, recovers the job from the HA store's
    completed-checkpoint pointer and resumes triggering.  ``stall_s``
    sleeps before the kill (a wedged-then-dead leader whose lease file
    goes stale while it still holds sockets open).  The cluster is
    expected to absorb the kill with zero lost and zero duplicated
    records: every stale-epoch completion, deploy and 2PC commit the
    zombie attempts afterwards is fenced."""

    def __init__(self, at: int = 1, times: int = 1, stall_s: float = 0.0):
        if times < 1:
            raise ValueError("KillCoordinator: times must be >= 1")
        self.at = at
        self.times = times
        self.stall_s = stall_s

    def action(self, n: int, rng: random.Random) -> Action:
        if self.at <= n < self.at + self.times:
            if self.stall_s > 0:
                # composite firing: hold the lease stale first, then die —
                # same slow-kill modeling as KillDuringRescale
                time.sleep(self.stall_s)
            return (FAIL, f"coordinator killed at lease renewal {n}")
        return OK


class FailWithProbability(FaultSchedule):
    """Fail each firing with probability ``p`` — drawn from the point's own
    seeded RNG, so the sequence is a pure function of (seed, point)."""

    def __init__(self, p: float):
        self.p = p

    def action(self, n: int, rng: random.Random) -> Action:
        return FAIL if rng.random() < self.p else OK


class FaultInjector:
    """Registry of fault points -> schedules with a deterministic seed.

    Each point gets its own firing counter, its own ``random.Random``
    seeded from ``f"{seed}:{point}"``, and its own action history — two
    runs with the same seed and schedules produce identical per-point
    histories no matter how unrelated threads interleave."""

    def __init__(self, seed: int = 0):
        self.seed = seed
        self._lock = threading.Lock()
        self._schedules: Dict[str, FaultSchedule] = {}
        self._counts: Dict[str, int] = {}
        self._rngs: Dict[str, random.Random] = {}
        self._history: Dict[str, List[Action]] = {}

    def inject(self, point: str, schedule: FaultSchedule) -> FaultSchedule:
        """Attach ``schedule`` to ``point`` (replacing any previous one);
        returns the schedule for later control (e.g. ``Partition.heal``)."""
        with self._lock:
            self._schedules[point] = schedule
            self._counts.setdefault(point, 0)
            self._history.setdefault(point, [])
        return schedule

    def clear(self, point: Optional[str] = None) -> None:
        with self._lock:
            if point is None:
                self._schedules.clear()
            else:
                self._schedules.pop(point, None)

    def _consult(self, point: str, ctx) -> Tuple[Optional[FaultSchedule],
                                                 Action, int]:
        """One firing: match, count, draw the action, record history."""
        with self._lock:
            sched = self._schedules.get(point)
            if sched is None or not sched.matches(ctx):
                return None, OK, 0
            n = self._counts.get(point, 0) + 1
            self._counts[point] = n
            rng = self._rngs.get(point)
            if rng is None:
                rng = self._rngs[point] = random.Random(
                    f"{self.seed}:{point}")
            act = sched.action(n, rng)
            self._history.setdefault(point, []).append(act)
        return sched, act, n

    def fire(self, point: str, **ctx) -> bool:
        """Consult the point's schedule: returns True to proceed, False to
        suppress delivery (``drop``), sleeps on ``delay``, parks on
        ``hang`` until the schedule heals, raises :class:`InjectedFault`
        on ``fail``."""
        sched, act, n = self._consult(point, ctx)
        if act == OK:
            return True
        if act == DROP:
            return False
        if act == HANG:
            # wedged: park until healed — the hang itself fired exactly
            # once, so determinism survives any wedge duration
            while sched.dropping():
                time.sleep(0.005)
            return True
        if isinstance(act, tuple) and act[0] == "delay":
            time.sleep(act[1])
            return True
        if isinstance(act, tuple) and act[0] == FAIL:
            raise InjectedFault(act[1])
        raise InjectedFault(f"injected fault at {point} (firing {n}, "
                            f"ctx={ctx or {}})")

    def skew(self, point: str, **ctx) -> float:
        """Clock-reading twin of :meth:`fire`: returns the schedule's skew
        offset in ms (``("skew", off)`` actions), 0.0 otherwise.  Each
        reading advances the point's counter/RNG/history like a firing."""
        _sched, act, _n = self._consult(point, ctx)
        if isinstance(act, tuple) and act[0] == "skew":
            return float(act[1])
        return 0.0

    def truncated(self, point: str, nbytes: int, **ctx) -> int:
        """Durable-write twin of :meth:`fire`: returns how many of the
        payload's ``nbytes`` actually persist.  One consult per call (the
        counter/RNG/history advance exactly once — never combine with a
        separate ``fire`` on the same point): ``("truncate", frac)``
        actions keep the first ``int(nbytes * frac)`` bytes, ``drop``
        persists nothing, ``delay``/``hang``/``fail`` behave exactly like
        :meth:`fire`, ``ok`` persists everything."""
        sched, act, n = self._consult(point, ctx)
        if act == OK:
            return nbytes
        if isinstance(act, tuple) and act[0] == "truncate":
            return int(nbytes * float(act[1]))
        if act == DROP:
            return 0
        if act == HANG:
            while sched.dropping():
                time.sleep(0.005)
            return nbytes
        if isinstance(act, tuple) and act[0] == "delay":
            time.sleep(act[1])
            return nbytes
        if isinstance(act, tuple) and act[0] == FAIL:
            raise InjectedFault(act[1])
        raise InjectedFault(f"injected fault at {point} (firing {n}, "
                            f"ctx={ctx or {}})")

    def blocked(self, point: str, **ctx) -> bool:
        """Is the point's schedule in a persistent drop state?  The poll
        primitive for partition-style stalls: a blocked sender re-checks
        until :meth:`Partition.heal` without advancing the firing counter,
        RNG or history — stall duration never corrupts determinism.  A
        one-shot ``drop`` (e.g. from an :class:`ActionSequence`) reads as
        not-blocked, so it delays a sender momentarily instead of hanging
        it forever.  Directional schedules only read blocked for matching
        ``ctx`` (same contract as :meth:`fire`)."""
        with self._lock:
            sched = self._schedules.get(point)
        return sched is not None and sched.dropping() and sched.matches(ctx)

    def history(self, point: Optional[str] = None):
        """Recorded action sequence of one point (or all points) — the
        determinism contract: compare across runs with the same seed."""
        with self._lock:
            if point is not None:
                return list(self._history.get(point, []))
            return {p: list(h) for p, h in self._history.items()}

    def fired(self, point: str) -> int:
        with self._lock:
            return self._counts.get(point, 0)

    def has_schedule(self, point: str) -> bool:
        with self._lock:
            return point in self._schedules


# ---------------------------------------------------------------------------
# global hook — the runtime's fault points call fire(); no injector = no-op
# ---------------------------------------------------------------------------

_ACTIVE: Optional[FaultInjector] = None


def install(injector: FaultInjector) -> FaultInjector:
    global _ACTIVE
    _ACTIVE = injector
    return injector


def uninstall() -> None:
    global _ACTIVE
    _ACTIVE = None


def active() -> Optional[FaultInjector]:
    return _ACTIVE


@contextmanager
def installed(injector: FaultInjector):
    """``with chaos.installed(inj): ...`` — scoped installation; always
    uninstalls, so one test's faults never leak into the next."""
    install(injector)
    try:
        yield injector
    finally:
        uninstall()


def fire(point: str, **ctx) -> bool:
    """The runtime-side hook: near-zero cost when no injector is installed."""
    inj = _ACTIVE
    if inj is None:
        return True
    return inj.fire(point, **ctx)


def blocked(point: str, **ctx) -> bool:
    """Poll a dropped point without re-firing it (counter/RNG/history stay
    untouched): a stalled sender loops on this until the partition heals."""
    inj = _ACTIVE
    return inj is not None and inj.blocked(point, **ctx)


def skew(point: str, **ctx) -> float:
    """Clock-reading hook (``utils/clock.py``): current skew offset in ms
    from an installed :class:`ClockSkew` schedule; 0.0 when no injector or
    no schedule — near-zero cost on the unskewed path."""
    inj = _ACTIVE
    if inj is None:
        return 0.0
    return inj.skew(point, **ctx)


def truncated(point: str, nbytes: int, **ctx) -> int:
    """Durable-write hook (checkpoint storage): how many of ``nbytes``
    persist at this fault point — ``nbytes`` when no injector/schedule."""
    inj = _ACTIVE
    if inj is None:
        return nbytes
    return inj.truncated(point, nbytes, **ctx)


# ---------------------------------------------------------------------------
# TCP-level injector (promoted from tests/test_nemesis.py)
# ---------------------------------------------------------------------------

class FreezableProxy:
    """TCP proxy that can stop forwarding bytes (packets 'drop' while both
    endpoints' sockets stay open) — a one-link network partition.

    Interpose it on a component's path to a real-socket service (object
    store, Kafka broker, worker control plane) and call :meth:`freeze` /
    :meth:`heal`; iptables-free, in-process, deterministic.

    :meth:`freeze` takes an optional ``direction`` for ASYMMETRIC
    partitions: ``"a->b"`` blackholes only client→server bytes (requests
    vanish, responses would flow), ``"b->a"`` only server→client
    (requests arrive, responses vanish), ``"both"`` (default) the classic
    full blackhole."""

    DIRECTIONS = ("both", "a->b", "b->a")

    def __init__(self, target_host: str, target_port: int):
        self.target = (target_host, target_port)
        self._srv = socket.create_server(("127.0.0.1", 0))
        self.port = self._srv.getsockname()[1]
        self.url = f"http://127.0.0.1:{self.port}"
        self._frozen = {"a->b": threading.Event(),
                        "b->a": threading.Event()}
        self._stop = threading.Event()
        self._threads = []
        t = threading.Thread(target=self._accept_loop, daemon=True)
        t.start()
        self._threads.append(t)

    def freeze(self, direction: str = "both") -> None:
        if direction not in self.DIRECTIONS:
            raise ValueError(f"direction must be one of {self.DIRECTIONS}")
        for d, ev in self._frozen.items():
            if direction in ("both", d):
                ev.set()

    def heal(self, direction: str = "both") -> None:
        if direction not in self.DIRECTIONS:
            raise ValueError(f"direction must be one of {self.DIRECTIONS}")
        for d, ev in self._frozen.items():
            if direction in ("both", d):
                ev.clear()

    def _accept_loop(self) -> None:
        self._srv.settimeout(0.2)
        while not self._stop.is_set():
            try:
                conn, _ = self._srv.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            try:
                up = socket.create_connection(self.target, timeout=5)
            except OSError:
                conn.close()
                continue
            for a, b, d in ((conn, up, "a->b"), (up, conn, "b->a")):
                t = threading.Thread(target=self._pump, args=(a, b, d),
                                     daemon=True)
                t.start()
                self._threads.append(t)

    def _pump(self, src: socket.socket, dst: socket.socket,
              direction: str) -> None:
        frozen = self._frozen[direction]
        src.settimeout(0.2)
        while not self._stop.is_set():
            try:
                data = src.recv(65536)
            except socket.timeout:
                continue
            except OSError:
                break
            if not data:
                break
            if frozen.is_set():
                # blackhole: this direction's bytes are DROPPED on the
                # floor (never queued — a heal must not deliver stale
                # in-flight traffic the sender already gave up on); the
                # sender neither errors nor progresses, exactly the
                # packets-vanish partition, while the opposite pump may
                # still be forwarding
                continue
            try:
                dst.sendall(data)
            except OSError:
                break
        for s in (src, dst):
            try:
                s.close()
            except OSError:
                pass

    def stop(self) -> None:
        self._stop.set()
        try:
            self._srv.close()
        except OSError:
            pass
