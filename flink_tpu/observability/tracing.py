"""The program's one span layer: :func:`span` / :func:`instant` /
:func:`complete` are the only way the runtime records a span, and every
span goes to two sinks.

- **The profiler's clock.**  Each span is a ``jax.profiler.TraceAnnotation``
  (TraceMe) of the same name, its keyword arguments the event's stats, so
  it lands on a ``/host:`` line of the ``.xplane.pb`` on the clock of
  ``XLA Ops`` whenever a profiler session is running in the process —
  whoever started it.  With no session (and no journal) ``span`` hands
  out one shared no-op, so the hot paths afford unconditional
  instrumentation and there is nothing to switch on.
- **The span journal**, a lock-free per-process ring, when one is
  installed with :func:`install` (``metrics.tracing.enabled``): the
  operator's own view, served as Chrome trace JSON by the REST API and
  merged across worker processes.  With no journal installed the emit
  helpers allocate no journal entry.

Beside the spans, the same module keeps **a thread's account of its own
time**: :class:`PhaseTimer` is a span that adds its wall time AND the
running thread's CPU time to a :class:`TimeAccount` (the window
operator's ``phase_ns``), and :func:`thread_cpu_ns` reads a thread's CPU
clock from outside it (``Task.cpu_ns``).  Wall minus CPU is what a span
alone cannot show: the time a thread waited for the GIL, a lock or the
device.

Design points:

- **Lock-free bounded ring**: span slots are reserved with one
  ``next()`` on an ``itertools.count`` — a single C call, atomic under
  the GIL — so concurrent recorders never contend on a mutex and every
  reserved slot has exactly one writer; once the capacity is exhausted
  new spans are DROPPED and counted (:attr:`SpanJournal.dropped`) —
  memory stays bounded no matter how hot the instrumented site is, and
  the drop counter makes truncation loud instead of silent.
- **Timestamps**: span begin/end use ``time.perf_counter_ns`` (monotone,
  ns precision — hot-stage phases are sub-ms); the journal anchors that
  clock to wall time THROUGH the ``utils/clock.py`` seam at creation, so
  exported timelines live on the (chaos-skewable) wall clock and
  cross-process assembly can align per-worker anchors.
- **Chrome trace-event export**: :func:`to_chrome` renders a journal
  snapshot as the trace-event JSON dialect Perfetto / chrome://tracing
  load directly (``ph: "X"`` complete spans, ``ph: "i"`` instants,
  metadata events naming processes/threads).

Beyond the standard library this module imports the clock seam and
``jax.profiler`` (which every runtime layer loads anyway), so any layer can
import it without cycles.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from flink_tpu.utils import clock

__all__ = ["SpanJournal", "install", "uninstall", "active", "enabled",
           "span", "instant", "complete", "CpuShare", "TimeAccount",
           "PhaseTimer", "thread_cpu_ns", "to_chrome",
           "acquire_for_execution", "release_after_execution"]

#: default ring capacity — ~8k spans cover minutes of checkpoint/phase
#: traffic; bench --trace installs a much larger ring explicitly
DEFAULT_CAPACITY = 8192


class SpanJournal:
    """Bounded per-process ring of structured spans.

    Each entry is a tuple ``(ph, ts_ns, dur_ns, name, cat, tid, args)``
    with ``ph`` one of ``"X"`` (complete span) / ``"i"`` (instant),
    ``ts_ns`` a ``perf_counter_ns`` reading, ``tid`` the recording
    thread's name and ``args`` a small dict of scalars (or None).
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 clock_: Optional["clock.Clock"] = None):
        self._cap = max(1, int(capacity))
        self._clock = clock_ if clock_ is not None else clock.SYSTEM_CLOCK
        self._buf: List[Optional[tuple]] = [None] * self._cap
        #: lock-free slot reservation: ``next()`` is one atomic C call,
        #: so the reservation count is exact under concurrent recording
        self._reserve = itertools.count()
        #: wall/perf anchor pair: maps perf_counter_ns readings onto the
        #: (chaos-skewable) wall clock at export time
        self.anchor_wall_us = int(self._clock.now_ms() * 1000)
        self.anchor_perf_ns = time.perf_counter_ns()

    # -- recording ---------------------------------------------------------
    def record(self, ph: str, ts_ns: int, dur_ns: int, name: str,
               cat: str, args: Optional[Dict[str, Any]] = None) -> None:
        i = next(self._reserve)        # atomic slot reservation
        if i >= self._cap:
            return                     # full: drop, counted via _reserved
        self._buf[i] = (ph, ts_ns, dur_ns, name, cat,
                        threading.current_thread().name, args)

    def _reserved(self) -> int:
        """Total reservations so far WITHOUT consuming a slot —
        ``itertools.count`` exposes its next value only through the
        pickle protocol (``count(n).__reduce__() == (count, (n,))``).
        Cold-path reads only (properties, snapshot)."""
        return self._reserve.__reduce__()[1][0]

    def reset(self) -> None:
        """Fresh ring + drop counter + anchors: a new job execution in the
        same process starts from an empty timeline instead of inheriting
        (or being starved by) the previous job's spans.  Spans a racing
        recorder is mid-writing when reset lands may bleed into the new
        ring — one stray span beats a dead or leaked trace."""
        fresh: List[Optional[tuple]] = [None] * self._cap
        # counter first, buffer second: a racing recorder that reserved
        # from the OLD counter writes a stale high slot into whichever
        # buffer it sees — spans() skips the stale None-gaps either way
        self._reserve = itertools.count()
        self._buf = fresh
        self.anchor_wall_us = int(self._clock.now_ms() * 1000)
        self.anchor_perf_ns = time.perf_counter_ns()

    @property
    def capacity(self) -> int:
        return self._cap

    @property
    def recorded(self) -> int:
        return min(self._reserved(), self._cap)

    @property
    def dropped(self) -> int:
        return max(0, self._reserved() - self._cap)

    # -- reading -----------------------------------------------------------
    def spans(self) -> List[tuple]:
        """Recorded spans in reservation order (in-flight writes — slots
        reserved but not yet stored by another thread — are skipped)."""
        return [s for s in self._buf[:self.recorded] if s is not None]

    def snapshot(self) -> Dict[str, Any]:
        """Picklable journal dump — the unit cross-process assembly ships
        (``assembly.merge_timelines``) and exporters render."""
        return {"anchor_wall_us": self.anchor_wall_us,
                "anchor_perf_ns": self.anchor_perf_ns,
                "spans": self.spans(),
                "dropped": self.dropped,
                "capacity": self._cap}

    def summary(self) -> Dict[str, Any]:
        """Monitoring-grade rollup (``job_status()["trace"]`` backing):
        span/drop counts plus per-category tallies."""
        cats: Dict[str, int] = {}
        for s in self.spans():
            cats[s[4]] = cats.get(s[4], 0) + 1
        return {"enabled": True, "spans": self.recorded,
                "dropped": self.dropped, "capacity": self._cap,
                "categories": cats}


# ---------------------------------------------------------------------------
# module singleton + emit helpers (the instrumentation-site API)
# ---------------------------------------------------------------------------

_JOURNAL: Optional[SpanJournal] = None


def install(journal: Optional[SpanJournal] = None,
            capacity: int = DEFAULT_CAPACITY) -> SpanJournal:
    """Install ``journal`` (or a fresh ring of ``capacity``) as THE
    process journal; returns it.  Instrumentation all over the runtime
    starts recording immediately."""
    global _JOURNAL
    _JOURNAL = journal if journal is not None else SpanJournal(capacity)
    return _JOURNAL


def uninstall() -> Optional[SpanJournal]:
    """Disable tracing; returns the journal that was installed (so its
    contents can still be exported)."""
    global _JOURNAL
    j, _JOURNAL = _JOURNAL, None
    return j


def active() -> Optional[SpanJournal]:
    return _JOURNAL


def enabled() -> bool:
    return _JOURNAL is not None


def adopt_or_install(capacity: int) -> "tuple[SpanJournal, bool]":
    """Constructor-time arm of the ownership state machine (shared by
    both cluster frontends): adopt the live ring — its installer owns its
    lifetime and capacity choice — else install an owned ring of
    ``capacity``.  Unlike :func:`acquire_for_execution` this never
    resets: construction must not clear a ring another job is still
    recording into."""
    act = active()
    if act is not None:
        return act, False
    return install(capacity=int(capacity)), True


def acquire_for_execution(journal: Optional[SpanJournal], owned: bool,
                          capacity: Optional[int] = None
                          ) -> "tuple[SpanJournal, bool]":
    """Claim the process journal for one job execution; returns the
    ``(journal, owned)`` pair the run will record into and report from.

    Both cluster frontends (MiniCluster.execute, ProcessCluster.run) go
    through this one state machine so the ownership invariants live in a
    single place:

    - **own ring, singleton free or ours**: re-install (a previous
      execution released it) and reset — job B must not inherit job A's
      spans or start against A's already-consumed capacity (the ring
      drops when full, so a long-lived process would go trace-dead).
    - **own ring, FOREIGN ring live**: re-adopt the live ring — our ring
      is not the one instrumentation records into, so installing or
      reporting from it would serve a stale timeline as this job's.
    - **adopted ring, singleton free**: its owner released it — stand up
      a fresh OWNED ring (``capacity`` or the adopted ring's) instead of
      running trace-dead while reporting the stale adopted spans.
    - **adopted or foreign ring live**: (re-)adopt it; the installer
      resets/releases it, not us.
    """
    act = active()
    if owned:
        if act is None or act is journal:
            install(journal)
            journal.reset()
            return journal, True
        return act, False
    if act is None:
        if capacity is None:
            capacity = (journal.capacity if journal is not None
                        else DEFAULT_CAPACITY)
        return install(capacity=int(capacity)), True
    return act, False


def release_after_execution(journal: Optional[SpanJournal],
                            owned: bool) -> None:
    """Release an OWNED ring at execution end so the next tracing-enabled
    cluster in this process installs fresh instead of adopting (and
    reporting) this job's spans; the caller's handle keeps serving
    job_status()/trace exports afterwards.  Adopted rings are the
    installer's to release — left untouched."""
    if owned and active() is journal:
        uninstall()


class _SpanCtx:
    """A span while a journal is installed: one complete journal entry on
    exit, and the profiler's annotation of the same name around it."""

    __slots__ = ("_name", "_cat", "_args", "_t0", "_j", "_traceme")

    def __init__(self, name: str, cat: str, args: dict):
        self._name = name
        self._cat = cat
        self._args = args or None
        self._traceme = TraceAnnotation(name, **args)

    def __enter__(self):
        self._traceme.__enter__()
        self._j = _JOURNAL
        if self._j is not None:
            self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        j = self._j
        if j is not None:
            t1 = time.perf_counter_ns()
            j.record("X", self._t0, t1 - self._t0, self._name, self._cat,
                     self._args)
        self._traceme.__exit__(*exc)
        return False


class _NoSpan:
    """What :func:`span` hands out while neither sink is open."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NO_SPAN = _NoSpan()


def span(name: str, cat: str = "runtime", **args):
    """Begin/end span context manager: a profiler annotation ``name`` with
    ``args`` as its stats and, with a journal installed, a ``ph: "X"``
    journal entry under ``cat``.  Spans on one thread nest; a span that
    outlives its block (``checkpoint.align``) is entered and exited by
    hand, on one thread, outside any span opened after it.  With no
    journal installed and no profiler session running the answer is one
    shared no-op (a quarter of the cost of an inert annotation, and the
    task threads share one GIL: PERF.md section 6, PR 37); a span that is
    open when a session starts is not in its trace."""
    if _JOURNAL is None:
        if not TraceAnnotation.is_enabled():
            return _NO_SPAN
        return TraceAnnotation(name, **args)
    return _SpanCtx(name, cat, args)


def instant(name: str, cat: str = "runtime", **args) -> None:
    """Point-in-time event: ``ph: "i"`` in the journal, a zero-length
    annotation in a profiler session."""
    j = _JOURNAL
    if j is not None:
        j.record("i", time.perf_counter_ns(), 0, name, cat, args or None)
    if TraceAnnotation.is_enabled():
        with TraceAnnotation(name, **args):
            pass


def complete(name: str, start_ns: int, end_ns: int,
             cat: str = "runtime", **args) -> None:
    """Complete span with explicit ``perf_counter_ns`` endpoints — for
    sites that already timed themselves and for spans that cross threads
    (checkpoint trigger→complete).  The profiler cannot be handed a past
    interval: it gets a zero-length annotation where the span ENDS, with
    the length as ``dur_ns``."""
    dur = max(0, end_ns - start_ns)
    j = _JOURNAL
    if j is not None:
        j.record("X", start_ns, dur, name, cat, args or None)
    if TraceAnnotation.is_enabled():
        with TraceAnnotation(name, dur_ns=dur, **args):
            pass


#: a thread's CPU clock is read at most this often per account and key.
#: ``time.thread_time_ns`` is a system call (no vDSO path): 0.36 us on a
#: plain Linux host, but 5.75 us on the TPU host's sealed VM, with the GIL
#: held — read on every entry of every phase it cost cell 1 a tenth of its
#: records per second, and ten times a second a phase still a part of one
#: per cent (chip, PR 37; PERF.md section 6)
CPU_READ_EVERY_NS = 1_000_000_000


class CpuShare:
    """When a region's next reading of the CPU clock is due, and the CPU
    share of its wall time that the last reading found.

    The clock is read for one entry of a region every
    ``CPU_READ_EVERY_NS`` (always for one entered more rarely: a fire, a
    cut); an entry in between is given the share of the last entry that
    was read, of its own wall time.  So a region's CPU time is exact where
    it is rare and an estimate from one reading a second where it runs per
    batch.  Where the thread's clock is exact (a plain Linux host) CPU <=
    wall.  Where it advances in scheduler ticks (the TPU host's VM: 10 ms)
    one reading of a short region is nothing or a whole tick: right on
    average, to be trusted only over some hundreds of readings, and a
    thread's total better from :func:`thread_cpu_ns`."""

    __slots__ = ("due", "share")

    def __init__(self):
        self.due = 0
        self.share = 1.0

    def settle(self, t0: int, wall: int, cpu: Optional[int]) -> int:
        """CPU ns of an entry begun at ``t0`` that took ``wall``: ``cpu``
        where the clock was read (``t0 >= due``), else the estimate."""
        if cpu is None:
            return int(wall * self.share)
        self.due = t0 + CPU_READ_EVERY_NS
        self.share = cpu / wall if wall else 1.0
        return cpu


class TimeAccount(dict):
    """``{key: wall ns, key + "_cpu": CPU ns}``, filled by
    :class:`PhaseTimer`: a plain dict to every reader (the window
    operator's ``phase_ns``).  Beside the entries it keeps each key's
    :class:`CpuShare`."""

    __slots__ = ("cpu_shares",)

    def __init__(self):
        super().__init__()
        self.cpu_shares: Dict[str, CpuShare] = {}


class PhaseTimer:
    """A span that also keeps the account of its thread's time: on exit
    ``acc[key]`` grows by the wall time (``perf_counter_ns``) and
    ``acc[key + "_cpu"]`` by the CPU time the running thread used
    (``thread_time_ns``, as often as :class:`CpuShare` says).  Wall minus
    CPU is time the thread was off the CPU: waiting for the GIL, the
    scheduler, a lock, or the device.  Whoever reads ``phase_ns["probe"]``
    reads ``phase_ns["probe_cpu"]`` the same way.  ``name`` None opens no
    span."""

    __slots__ = ("_acc", "_key", "_span", "_t0", "_c0", "_share")

    def __init__(self, acc: TimeAccount, key: str, name: Optional[str],
                 cat: str = "runtime", args: Optional[Dict[str, Any]] = None):
        self._acc = acc
        self._key = key
        if name is None:
            self._span = _NO_SPAN
        elif args:
            self._span = span(name, cat, **args)
        else:
            self._span = span(name, cat)

    def __enter__(self):
        self._span.__enter__()
        share = self._acc.cpu_shares.get(self._key)
        if share is None:
            share = self._acc.cpu_shares[self._key] = CpuShare()
        self._share = share
        # the CPU reading inside the wall reading: cpu <= wall holds
        self._t0 = t0 = time.perf_counter_ns()
        self._c0 = time.thread_time_ns() if t0 >= share.due else None
        return self

    def __exit__(self, *exc):
        c0 = self._c0
        cpu = None if c0 is None else time.thread_time_ns() - c0
        t0 = self._t0
        wall = time.perf_counter_ns() - t0
        acc, key = self._acc, self._key
        acc[key] = acc.get(key, 0) + wall
        key += "_cpu"
        acc[key] = acc.get(key, 0) + self._share.settle(t0, wall, cpu)
        self._span.__exit__(*exc)
        return False


def thread_cpu_ns(thread: Optional[threading.Thread]) -> Optional[int]:
    """CPU time ``thread`` has used so far, read from OUTSIDE it (its
    POSIX CPU clock): costs the watched thread nothing.  None for a thread
    that is not running (its clock is gone) or where the platform has no
    such clock."""
    if thread is None or thread.ident is None or not thread.is_alive():
        return None
    try:
        return time.clock_gettime_ns(time.pthread_getcpuclockid(thread.ident))
    except (AttributeError, OSError):
        return None


# ---------------------------------------------------------------------------
# Chrome trace-event export
# ---------------------------------------------------------------------------

def to_chrome(snap: Dict[str, Any], pid: int = 0,
              process_name: str = "flink-tpu",
              offset_us: float = 0.0) -> List[Dict[str, Any]]:
    """Render a journal snapshot as Chrome trace-event dicts
    (Perfetto-loadable).  ``offset_us`` shifts this journal's wall
    timeline — cross-process assembly passes the estimated per-worker
    clock offset so every process lands on ONE job timeline."""
    wall0 = snap["anchor_wall_us"] + offset_us
    perf0 = snap["anchor_perf_ns"]
    events: List[Dict[str, Any]] = [{
        "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
        "args": {"name": process_name}}]
    seen_tids: Dict[str, int] = {}
    for ph, ts_ns, dur_ns, name, cat, tname, args in snap["spans"]:
        tid = seen_tids.setdefault(tname, len(seen_tids) + 1)
        ev: Dict[str, Any] = {
            "name": name, "cat": cat, "ph": ph, "pid": pid, "tid": tid,
            "ts": round(wall0 + (ts_ns - perf0) / 1000.0, 3)}
        if ph == "X":
            ev["dur"] = round(dur_ns / 1000.0, 3)
        elif ph == "i":
            ev["s"] = "t"                  # thread-scoped instant
        if args:
            ev["args"] = dict(args)
        events.append(ev)
    for tname, tid in seen_tids.items():
        events.append({"ph": "M", "name": "thread_name", "pid": pid,
                       "tid": tid, "args": {"name": tname}})
    return events
