"""Profiling: the host-loop occupancy profiler + flight recorder, and the
jax.profiler device-trace wrappers.

Two lenses live here:

**Device lens** (the original thin wrapper): ``Profiler.start/stop``
captures an XLA trace (TensorBoard/Perfetto timelines); the program's own
stages reach it through ``observability.stats.StageSpan``.

**Host-loop lens** (the continuous occupancy profiler): the silo's wall
time is one event loop, and at closed-loop saturation the residual
queue-wait is loop *contention* — host turns, the device tick's
sync-materialize, the socket pump, and our own observability machinery
all time-share it. :class:`LoopProfiler` measures where that loop time
actually goes, continuously and cheaply enough to leave on:

* **Interposition** (py3.10-safe — no eager task factory, no loop
  subclass needed on a running loop): :func:`install_loop_profiler`
  shadows the loop instance's ``call_soon`` / ``call_at`` /
  ``call_soon_threadsafe`` with wrappers that time every callback the
  loop runs. ``call_later`` funnels through the patched ``call_at``;
  gaps between callbacks accrue to ``idle`` — so occupancy shares sum to
  ~1.0 of wall time by construction. Uninstall deletes the instance
  attributes, restoring the class methods (refcounted per loop: the last
  silo to stop removes the hooks; co-hosted silos share one profiler
  because occupancy is a property of the LOOP, not the silo).
* **Attribution**: each callback defaults to the category riding the
  :data:`LOOP_CATEGORY` contextvar (task steps run in the task's context,
  so one ``enter``/``mark_loop_category`` at the top of a turn/pump task
  labels every later step of that task); instrumented sites segment
  finer with :meth:`LoopProfiler.set_category`. A stage span
  (``observability.stats.StageSpan``) opened on the loop's thread keys
  loop time on itself: ``stage:<name>``, innermost open stage first, so
  a stage books its self time.
* **The loop account** (a metrics-enabled silo installs it; the
  profiler's ``stats``): at a callback boundary at least every
  :data:`FOLD_S` of loop time the seconds are folded into the silo's
  ``StatsRegistry`` — ``loop.idle.seconds`` (in ``select()``),
  ``loop.stage.<stage>.seconds``, ``loop.other.<category>.seconds`` —
  which sum to the wall clock over any interval; a callback longer than
  the interpreter's switch interval is a stop: ``loop.stop.seconds`` and
  the counter ``loop.stops.<stage or category>`` of what held most of it.
* **Flight recorder**: per-window occupancy slices plus the top-K
  slowest callbacks (category + grain class/method label when the turn
  declared one) land in a bounded ring; :meth:`LoopProfiler.trigger`
  snapshots the ring on anomalies — load-shed, watchdog lag,
  queue-wait-trend breach, tail-retained traces — rate-limited per
  reason, into a bounded snapshot deque the management surface serves.

Disabled (``SiloConfig.profiling_enabled`` and ``metrics_enabled`` both
False, the default) nothing is installed: the loop keeps its class
methods, hot paths pay one ``None`` check per site, and the off path is
structurally zero-overhead. ``metrics_enabled`` alone installs the
account; ``profiling_enabled`` adds the flight-recorder ring, the
triggers and the management payload.
"""

from __future__ import annotations

import asyncio
import contextlib
import contextvars
import functools
import logging
import sys
import time
import weakref
from collections import deque
from typing import Iterator

import jax

from .stats import bind_loop_account

log = logging.getLogger("orleans.profiling")

# native per-callback runner (native/hotloop.c): the same accounting as
# LoopProfiler._run_cb compiled to C (~0.2us vs ~1.3us per callback).
# None when the toolchain is unavailable or ORLEANS_TPU_NATIVE=0 — the
# pure-Python path below is the behavioural reference and the fallback.
# Linux-only: the C side stamps CLOCK_MONOTONIC, which shares a base
# with time.perf_counter ONLY on Linux — on e.g. macOS the two clocks
# diverge by cumulative system-sleep time, and the Python slow paths
# (flush/finalize/profile) compare perf_counter against C-written marks.
try:
    if sys.platform.startswith("linux"):
        from ..native import load as _load_native
        _hotloop = _load_native("_hotloop")
    else:
        _hotloop = None
except Exception:  # noqa: BLE001 — native must never break import
    _hotloop = None

__all__ = ["Profiler", "LoopProfiler", "LOOP_CATEGORIES", "LOOP_CATEGORY",
           "install_loop_profiler", "uninstall_loop_profiler",
           "loop_profiler", "mark_loop_category"]


# ---------------------------------------------------------------------------
# Host-loop occupancy profiler
# ---------------------------------------------------------------------------

# the named occupancy buckets loop time in no stage span is attributed
# into (a stage span keys the time it holds on ``stage:<name>``: the
# engine's claim and completion are engine.claim / engine.complete;
# staging, operand upload, kernel dispatch and the host materialize of a
# device tick run on the engine's worker thread and are not loop time)
LOOP_CATEGORIES = (
    "turns",          # host grain turns (dispatcher._run_turn)
    "timers",         # __timer__ tick turns + timer machinery
    "pump",           # socket pump + wire decode + batched routing
    "egress",         # outbound wire: response/request encode + sender
                      # writes (per-endpoint sender tasks, gateway
                      # client-route batch writes) — the slice sharded
                      # egress (SiloConfig.egress_shards) moves onto
                      # shard loops; its main-loop share is the ISSUE-15
                      # acceptance A/B
    "client",         # client-side gateway machinery sharing the loop
                      # (GatewayClient pumps/senders/reconnector) — split
                      # out of "other" so harness cost is separately
                      # attributable from silo cost in loop_attribution
    "storage",        # storage & journal provider IO awaited on-loop
    "observability",  # sampler/tracer/exporter internals
    "other",          # unattributed callbacks
    "idle",           # the loop waiting in select()
)

# Ambient default category for the CURRENT task/callback. Task steps run
# in the task's own context, so setting this once at the top of a task
# (dispatcher turn, socket pump, sampler loop) labels every later step of
# that task without per-step work; the interposition wrapper reads it at
# each callback start.
LOOP_CATEGORY: contextvars.ContextVar[str] = contextvars.ContextVar(
    "orleans_loop_category", default="other")

# 3.12+ eager task factories: ``asyncio.tasks._eager_tasks`` is the
# interpreter's registry of tasks CURRENTLY executing their first step
# eagerly (occupied for exactly that stretch). ``enter()`` consults it
# to guard the live-slice switch (see its docstring). On interpreters
# without eager factories (py3.10/3.11) this is None and the guard is a
# single constant test — the reference environment's behavior is
# unchanged.
_EAGER_TASKS = getattr(getattr(asyncio, "tasks", None), "_eager_tasks", None)

# the account folds into its stats registry at a callback boundary at
# least this often (seconds of loop time): a 20 s window's delta is then
# exact to a quarter of a per cent with no read-side flush
FOLD_S = 0.05
LOOP_STOP = "loop.stop.seconds"


def _stat_name(category: str) -> str:
    """The registry histogram a loop category folds into."""
    if category == "idle":
        return "loop.idle.seconds"
    if category.startswith("stage:"):
        return f"loop.stage.{category[6:]}.seconds"
    return f"loop.other.{category}.seconds"


def _stop_name(category: str) -> str:
    """The counter of the stops a stage or category held most of."""
    return "loop.stops." + (category[6:] if category.startswith("stage:")
                            else category)


def mark_loop_category(category: str) -> None:
    """Tag the current task so its future steps default to ``category``
    (no-op cost when no profiler is installed — it only sets a
    contextvar the wrapper would read)."""
    LOOP_CATEGORY.set(category)


def _describe_callback(cb) -> str:
    """Best-effort label for an unlabeled slow callback. Task steps name
    their coroutine; everything else falls back to the qualname."""
    owner = getattr(cb, "__self__", None)
    if owner is not None:
        get_coro = getattr(owner, "get_coro", None)
        if get_coro is not None:
            try:
                coro = get_coro()
                return getattr(coro, "__qualname__", None) or repr(coro)
            except Exception:  # noqa: BLE001 — labels are best-effort
                pass
    return getattr(cb, "__qualname__", None) or type(cb).__name__


class LoopProfiler:
    """Continuous occupancy accounting for ONE event loop.

    Single-threaded by construction (every mutation happens on the loop);
    the only cross-thread entry is the ``call_soon_threadsafe`` wrapper,
    which merely wraps the callback — timing runs loop-side.

    ``window`` seconds of attribution roll into one slice dict appended
    to ``ring`` (the flight-recorder substrate); ``snapshots`` holds
    anomaly-triggered copies of the ring. ``totals`` accumulates per
    category since install — the management read. ``stats`` (set by
    :meth:`attach`) is the loop account's registry: see the module doc."""

    __slots__ = ("window", "top_k", "trigger_interval", "ring",
                 "snapshots", "trigger_counts", "trigger_hooks", "totals",
                 "last_shares", "closed", "started", "stats", "stop_s",
                 "_win_start", "_win_cats", "_win_top", "_top_min",
                 "_last_end", "_depth", "_mark", "_cur", "_cb_label",
                 "_last_trigger", "_next_slow", "_start", "_adopted",
                 "_segs", "_folded", "_names")

    def __init__(self, window: float = 1.0, ring: int = 120,
                 top_k: int = 8, trigger_interval: float = 1.0,
                 max_snapshots: int = 8):
        self.window = window
        self.top_k = top_k
        self.trigger_interval = trigger_interval
        self.ring: deque[dict] = deque(maxlen=ring)
        self.snapshots: deque[dict] = deque(maxlen=max_snapshots)
        self.trigger_counts: dict[str, int] = {}
        self.trigger_hooks: list = []  # called with each new snapshot
        self.totals: dict[str, float] = {}
        self.last_shares: dict[str, float] = {}
        self.closed = False
        now = time.perf_counter()
        self.started = now
        self._win_start = now
        self._win_cats: dict[str, float] = {}
        # (duration, category, label, within-window start offset)
        self._win_top: list[tuple[float, str, str, float | None]] = []
        self._top_min = 0.0      # admission bar for the top-K record path
        self._last_end = now     # end of the previous callback (idle from)
        self._depth = 0          # >0 while inside a wrapped callback
        self._mark = now         # last attribution boundary
        self._cur = "other"      # category accruing since _mark
        self._cb_label: str | None = None
        self._last_trigger: dict[str, float] = {}
        self.stats = None
        # a callback longer than the interpreter's switch interval is a
        # stop: every other thread (the tick worker) waited for it too
        self.stop_s = sys.getswitchinterval()
        self._next_slow = now + window  # the periodic slow path (_slow)
        self._start = now        # start of the open callback
        self._adopted = False    # the open callback is the installing one
        self._segs: dict[str, float] = {}  # open callback's seconds by cat
        self._folded: dict[str, float] = {}  # window seconds already folded
        self._names: dict[str, str] = {}     # category -> histogram name

    # -- interposition side ------------------------------------------------
    def _entry(self):
        """The ONE callable every schedule reuses (scheduled with the
        real callback as its first argument — no per-callback closure)."""
        return self._run_cb

    def _wrap(self, cb):
        """Compatibility/test shim around :meth:`_entry`. The installed
        loop hooks do NOT use this — they schedule the entry callable
        with the real callback as its first argument, so the steady
        state allocates no closure per scheduled callback."""
        return functools.partial(self._entry(), cb)

    def _run_cb(self, cb, *args,
                _perf=time.perf_counter, _get_cat=LOOP_CATEGORY.get):
        """Execute one scheduled callback inside occupancy boundaries.
        This runs for EVERY callback the loop executes while profiling is
        on, so the steady state is kept flat and allocation-free: two
        clock reads, one contextvar get, two dict upserts (idle gap +
        category slice — cumulative ``totals`` are folded in once per
        window, not per callback), zero extra frames. The slow-callback
        path only engages for callbacks slower than the current window's
        admission bar (``_top_min``) or the stop threshold, and the fold
        and the window's finalize only when ``_next_slow`` is due;
        ``_perf``/``_get_cat`` are default-arg locals. A closed profiler
        passes straight through (callbacks scheduled before uninstall may
        still run after)."""
        if self.closed:
            return cb(*args)
        if self._depth and not self._adopted:
            # nested invocation (a wrapped fn called synchronously from
            # inside another): inner boundaries are a no-op
            self._depth += 1
            try:
                return cb(*args)
            finally:
                self._depth -= 1
        now = _perf()
        if self._depth:
            # the loop runs a callback only after the one before
            # returned: the callback that installed the account ended
            self._adopted = False
            self._close(None, now)
        gap = now - self._last_end
        wc = self._win_cats
        if gap > 0.0:
            # the loop was in select() between callbacks: idle
            # (try/except: the key exists after the window's first gap)
            try:
                wc["idle"] += gap
            except KeyError:
                wc["idle"] = gap
        self._depth = 1
        self._mark = self._start = now
        self._cur = _get_cat()
        self._cb_label = None
        try:
            return cb(*args)
        finally:
            self._close(cb, _perf())

    def _close(self, cb, end: float) -> None:
        """One callback's epilogue (the C runner's ``close_callback``):
        its last segment, the slow-callback record, the periodic slow
        path."""
        self._depth = 0
        d = end - self._mark
        dur = end - self._start
        slow = dur > self._top_min or dur > self.stop_s
        segs = self._segs
        if d > 0.0:
            if segs and slow:
                self._seg_add(d)
            else:
                # re-read the dict slot: robust against anything inside
                # cb ever rebinding the open window
                wc = self._win_cats
                cat = self._cur
                try:
                    wc[cat] += d
                except KeyError:
                    wc[cat] = d
        self._last_end = end
        if slow:
            # recorded under what held most of it (rare by construction:
            # the top-K bar rises to the K-th slowest as the window
            # fills; a stop is longer than the switch interval)
            owner = max(segs, key=segs.get) if segs else self._cur
            self._record_top(cb, dur, self._start - self._win_start, owner)
        if segs:
            segs.clear()
        if end >= self._next_slow:
            self._slow(end)

    def _seg_add(self, d: float) -> None:
        cat = self._cur
        wc = self._win_cats
        wc[cat] = wc.get(cat, 0.0) + d
        self._segs[cat] = self._segs.get(cat, 0.0) + d

    def _record_top(self, cb, dur: float, offset: float | None = None,
                    owner: str | None = None) -> None:
        """A slow callback: into the window's top-K if it passes the
        bar, and — longer than ``stop_s``, with the account on — a stop
        (``loop.stop.seconds`` and ``loop.stops.<owner>``). ``owner`` is
        the category that held most of it (the current one by default).
        ``offset`` = the callback's START relative to the open window's
        start (stamped by the hot path — C runner or the Python
        reference — so the Perfetto flame row places each record
        exactly instead of laying durations end-to-end from the window
        start). None only from legacy callers; the exporter falls back
        to cursor placement then."""
        cat = self._cur if owner is None else owner
        if dur > self._top_min:
            top = self._win_top
            top.append((dur, cat,
                        self._cb_label or _describe_callback(cb), offset))
            if len(top) > self.top_k:
                top.sort(key=lambda t: t[0], reverse=True)
                del top[self.top_k:]
                self._top_min = top[-1][0]
        st = self.stats
        if dur > self.stop_s and st is not None:
            st.observe(LOOP_STOP, dur)
            st.increment(_stop_name(cat))

    def _accrue(self, now: float) -> None:
        d = now - self._mark
        if d > 0.0:
            cat = self._cur
            self._win_cats[cat] = self._win_cats.get(cat, 0.0) + d
        self._mark = now

    # -- attribution side (instrumented runtime sites) ---------------------
    def set_category(self, category: str, label=None, *,
                     _perf=time.perf_counter) -> None:
        """Attribute loop time from here to the next boundary to
        ``category`` (segmenting WITHIN the current callback). Outside a
        wrapped callback this is a no-op: there is no loop time to
        attribute, and a stale mark must not accrue. ``label`` may be a
        string or a tuple of parts — tuples are joined with "." only if
        the callback actually lands in the top-K record (the per-turn
        hot path never pays the format). Accrual is inlined — this runs
        twice per device tick and twice per host turn."""
        if not self._depth or self.closed:
            return
        now = _perf()
        d = now - self._mark
        if d > 0.0:
            self._seg_add(d)
        self._mark = now
        self._cur = category
        if label is not None:
            self._cb_label = label

    def switch(self, category: str) -> str:
        """Switch the current callback's category and return the one it
        had, for the caller to switch back to before the callback ends
        (a stage span that opens and closes in one callback)."""
        prev = self._cur
        self.set_category(category)
        return prev

    def enter(self, category: str, label: str | None = None):
        """Category for the current slice AND the current task's future
        steps (turn bodies suspend; their resumptions must keep the
        label). Returns a token for :meth:`exit` — token discipline
        mirrors the dispatcher's contextvar usage across one task.

        Eager-aware guarded boundary (3.12+ eager task factories): an
        eagerly-executed first step runs INSIDE the callback that
        created the task, so a live-slice switch here would bleed into
        the creator's remaining frame if the step suspends (exit only
        runs on completion, in a LATER callback). The guard consults the
        interpreter's own eager-task registry (``asyncio.tasks``'
        ``_eager_tasks``, the set a task occupies exactly while its
        first step executes eagerly): inside an eager step the live
        switch is DEFERRED — the contextvar alone labels the task's
        post-suspension steps (read at each callback start), and the
        inline stretch stays honestly booked to the creator's category,
        which is where it physically ran. On interpreters without eager
        factories (the py3.10 reference environment) the registry does
        not exist, the guard is a single module-constant None test, and
        the switch is exact as before. The label still names the
        creator's callback when nothing has (a slow callback that ran a
        turn inline is that turn's, for the top-K)."""
        token = LOOP_CATEGORY.set(category)
        if _EAGER_TASKS is not None and self._depth:
            try:
                t = asyncio.current_task()
            except RuntimeError:
                t = None
            if t is not None and t in _EAGER_TASKS:
                # deferred: guarded eager boundary
                if label is not None and self._cb_label is None:
                    self._cb_label = label
                return token
        self.set_category(category, label)
        return token

    def exit(self, token) -> None:
        try:
            LOOP_CATEGORY.reset(token)
        except ValueError:
            # closed in another context than it opened in (a span whose
            # unit of work ends in a done-callback): that one never saw it
            pass
        self.set_category(LOOP_CATEGORY.get())

    def hold(self, category: str) -> tuple:
        """:meth:`enter` for a stage span held across an ``await``;
        :meth:`release` undoes it."""
        prev, start = self._cur, self._start
        return self.enter(category), prev, start

    def release(self, held: tuple) -> None:
        """Undo :meth:`hold`: a later step of the task books what its
        context says; the callback that held goes back to what it was
        booking (the nesting stage the held one closed inside)."""
        token, prev, start = held
        self.exit(token)
        if start == self._start:
            self.set_category(prev)

    def adopt(self, _perf=time.perf_counter) -> None:
        """Book the calling callback — the one installing the account,
        which no runner opened — from now on: the next runner entry,
        which the loop makes only once it has returned, closes it (its
        time is busy, not idle)."""
        if self._depth or self.closed:
            return
        self._cur = LOOP_CATEGORY.get()
        self._cb_label = None
        self._mark = self._start = _perf()
        self._depth = 1
        self._adopted = True

    # -- the loop account (the stats registry) -----------------------------
    def attach(self, stats) -> None:
        """Fold this loop's seconds into ``stats`` from now on (every
        :data:`FOLD_S` at the latest) and record its stops there."""
        self.stats = stats
        stats.histogram(LOOP_STOP)  # 0 and not absent without a stop
        self._next_slow = min(self._next_slow,
                              time.perf_counter() + FOLD_S)

    def _slow(self, now: float) -> None:
        """Due at ``_next_slow`` (a callback boundary): the fold, and
        the window's finalize once it is full."""
        if self.stats is not None:
            self._fold()
        if now - self._win_start >= self.window:
            self._finalize_window(now)
        nxt = self._win_start + self.window
        self._next_slow = nxt if self.stats is None \
            else min(nxt, now + FOLD_S)

    def _fold(self) -> None:
        """Observe what the open window accrued since the last fold."""
        st = self.stats
        folded = self._folded
        names = self._names
        for k, v in self._win_cats.items():
            d = v - folded.get(k, 0.0)
            if d > 0.0:
                name = names.get(k)
                if name is None:
                    name = names[k] = _stat_name(k)
                st.observe(name, d)
                folded[k] = v

    # -- windows / flight recorder ----------------------------------------
    def _finalize_window(self, now: float) -> None:
        if self.stats is not None:
            self._fold()
        wall = now - self._win_start
        shares = ({k: round(v / wall, 4) for k, v in self._win_cats.items()}
                  if wall > 0 else {})
        # cumulative totals are folded once per window, not per callback
        # (the hot path touches only _win_cats)
        tot = self.totals
        for k, v in self._win_cats.items():
            tot[k] = tot.get(k, 0.0) + v
        self._win_top.sort(key=lambda t: t[0], reverse=True)
        self.ring.append({
            "ts": time.time(),
            "wall_s": round(wall, 6),
            "seconds": {k: round(v, 6) for k, v in self._win_cats.items()},
            "shares": shares,
            "top": [{"seconds": round(d, 6), "category": c,
                     "label": lb if isinstance(lb, str)
                     else ".".join(str(p) for p in lb),
                     # within-window start offset: exact flame-row
                     # placement (None only via legacy _record_top calls)
                     "offset": None if off is None else round(off, 6)}
                    for d, c, lb, off in self._win_top[:self.top_k]],
        })
        self.last_shares = shares
        self._win_cats = {}
        self._folded = {}
        self._win_top = []
        self._top_min = 0.0
        self._win_start = now

    def _flush(self) -> None:
        """Force an attribution boundary so reads see everything up to
        now (reads run inside a callback — a ctl turn — so depth > 0)."""
        if self._depth and not self.closed:
            self._accrue(time.perf_counter())

    def trigger(self, reason: str, **attrs) -> dict | None:
        """Anomaly hook: snapshot the ring (plus the partial current
        window) into ``snapshots``. Rate-limited per reason so a shed
        storm yields one snapshot per ``trigger_interval``, not one per
        message; every trigger still counts."""
        self.trigger_counts[reason] = self.trigger_counts.get(reason, 0) + 1
        now = time.monotonic()
        if now - self._last_trigger.get(reason, -1e9) < self.trigger_interval:
            return None
        self._last_trigger[reason] = now
        self._flush()
        snap = {
            "reason": reason,
            "ts": time.time(),
            "attrs": attrs,
            "slices": list(self.ring),
            "current": {
                "seconds": {k: round(v, 6)
                            for k, v in self._win_cats.items()},
                "window_open_s": round(
                    time.perf_counter() - self._win_start, 6),
            },
        }
        self.snapshots.append(snap)
        for hook in self.trigger_hooks:
            try:
                hook(snap)
            except Exception:  # noqa: BLE001 — a sink must not break the loop
                log.exception("flight-recorder trigger hook failed")
        return snap

    # -- reads -------------------------------------------------------------
    def _cumulative(self) -> dict[str, float]:
        """Finalized-window totals plus the open window's accrual (the
        hot path folds into ``totals`` only at window boundaries)."""
        self._flush()
        out = dict(self.totals)
        for k, v in self._win_cats.items():
            out[k] = out.get(k, 0.0) + v
        return out

    def occupancy(self) -> dict[str, float]:
        """Cumulative per-category shares of accounted wall time
        (busy + idle); sums to ~1.0 by construction."""
        cum = self._cumulative()
        wall = sum(cum.values())
        if wall <= 0:
            return {}
        return {k: v / wall for k, v in cum.items()}

    def profile(self, windows: int = 20,
                snapshots: bool = True) -> dict:
        """The management-surface payload: cumulative seconds + shares,
        the last ``windows`` slices, and (optionally) the flight-recorder
        snapshots."""
        cum = self._cumulative()
        wall = sum(cum.values())
        out = {
            "window_s": self.window,
            "wall_s": round(wall, 6),
            "seconds": {k: round(v, 6) for k, v in cum.items()},
            "shares": {k: round(v / wall, 4)
                       for k, v in cum.items()} if wall else {},
            "windows": list(self.ring)[-windows:] if windows else [],
            "triggers": dict(self.trigger_counts),
        }
        if snapshots:
            out["snapshots"] = list(self.snapshots)
        return out


class _NativeLoopProfiler(LoopProfiler):
    """LoopProfiler whose per-callback hot path runs in C
    (native/hotloop.c). The C ``Runner`` owns the hot state — attribution
    boundary, open-window category dict, top-K admission bar, depth/
    closed flags — and every Python slow path (window finalize, trigger,
    flush, enter/exit) keeps working unchanged through the delegating
    properties installed below, which read and write the very same C
    struct members. Semantics are identical to the pure-Python parent
    (the behavioural reference, still exercised by the unit tests and
    the ``ORLEANS_TPU_NATIVE=0`` fallback)."""

    __slots__ = ("_c",)

    def __init__(self, *args, **kwargs):
        # the runner must exist BEFORE the parent __init__ writes state
        # through the delegating properties
        object.__setattr__(self, "_c", _hotloop.Runner(LOOP_CATEGORY, self))
        super().__init__(*args, **kwargs)

    def _entry(self):
        return self._c  # the Runner IS the scheduled callable

    def set_category(self, category: str, label=None) -> None:
        self._c.set_category(category, label)

    def adopt(self) -> None:
        self._c.adopt()


def _delegate(cname: str) -> property:
    return property(lambda self, _n=cname: getattr(self._c, _n),
                    lambda self, v, _n=cname: setattr(self._c, _n, v))


for _name, _cname in (("window", "window"), ("closed", "closed"),
                      ("_win_start", "win_start"), ("_win_cats", "win_cats"),
                      ("_top_min", "top_min"), ("_last_end", "last_end"),
                      ("_depth", "depth"), ("_mark", "mark"),
                      ("_cur", "cur"), ("_cb_label", "cb_label"),
                      ("_next_slow", "next_slow"), ("stop_s", "stop_s"),
                      ("_adopted", "adopted"), ("_start", "start")):
    setattr(_NativeLoopProfiler, _name, _delegate(_cname))
del _name, _cname


def _profiler_class() -> type[LoopProfiler]:
    return LoopProfiler if _hotloop is None else _NativeLoopProfiler


# one interposition per loop, refcounted: loop -> [refs, profiler,
# originals]. Weakly keyed: a loop abandoned without uninstall (a silo
# that died mid-start, a test loop dropped on the floor) must not leave
# an entry behind — id() reuse on a later loop would alias it onto the
# stale closed profiler and silently skip installing hooks.
_loop_profilers: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def loop_profiler(loop) -> LoopProfiler | None:
    """The profiler installed on ``loop``, or None."""
    ent = _loop_profilers.get(loop)
    return ent[1] if ent else None


def install_loop_profiler(loop, *, window: float = 1.0, ring: int = 120,
                          top_k: int = 8, trigger_interval: float = 1.0,
                          stats=None) -> LoopProfiler:
    """Interpose occupancy accounting on ``loop`` (idempotent +
    refcounted: silos sharing a loop share ONE profiler — occupancy is a
    loop property — and the last :func:`uninstall_loop_profiler` removes
    the hooks). ``stats``: the registry the loop account folds into (the
    first silo that brings one). Installed from the loop's own thread,
    the stage spans of that thread key on it, callbacks scheduled or
    registered before the install are wrapped where they wait, and the
    callback running the install is booked from here on
    (:meth:`LoopProfiler.adopt`). Instance-attribute shadowing keeps
    this py3.10-safe: no loop subclass, no task factory, works on a loop
    that is already running. ``call_later`` is covered through the patched ``call_at``;
    executor completions arrive via the patched ``call_soon_threadsafe``;
    selector IO-ready callbacks (transport ``_read_ready`` — the recv
    syscall + buffer feed that would otherwise land in the inter-callback
    gap and be booked as idle) are covered through the patched
    ``_add_reader``/``_add_writer`` and attributed to ``pump`` (in this
    runtime an FD becoming readable IS fabric/gateway socket work).

    Known tradeoff: scheduling hooks prepend the runner via C-level
    ``functools.partial`` — no Python frame per schedule, which is the
    whole overhead budget — so asyncio's callable check inspects the
    runner, not the user callback; a non-callable (e.g. a bare
    coroutine object) fails inside the Handle via the loop exception
    handler instead of raising TypeError at the buggy call site. A
    pre-validating Python wrapper would re-add the per-schedule frame
    this design exists to avoid."""
    ent = _loop_profilers.get(loop)
    if ent is not None:
        ent[0] += 1
        prof = ent[1]
        if stats is not None and prof.stats is None:
            prof.attach(stats)
        if ring > (prof.ring.maxlen or 0):
            prof.ring = deque(prof.ring, maxlen=ring)
        return prof
    prof = _profiler_class()(window=window, ring=ring, top_k=top_k,
                             trigger_interval=trigger_interval)
    if stats is not None:
        prof.attach(stats)
    # the ONE entry callable every schedule reuses (the C Runner when
    # native, the bound _run_cb otherwise): scheduling it with the real
    # callback as its first argument costs no closure/partial allocation
    # per callback (the dominant interposition tax otherwise).
    # call_soon/call_soon_threadsafe prepend it via a C-level
    # functools.partial — zero Python frames on the schedule path:
    #   loop.call_soon(cb, *a, context=c)
    #     -> orig_call_soon(run_cb, cb, *a, context=c)
    # call_at needs a real wrapper (``when`` precedes the callback), and
    # timers are orders of magnitude rarer than call_soon.
    run_cb = prof._entry()
    call_soon = functools.partial(loop.call_soon, run_cb)
    call_soon_threadsafe = functools.partial(loop.call_soon_threadsafe,
                                             run_cb)

    def call_at(when, callback, *args, context=None,
                _at=loop.call_at, _run=run_cb):
        return _at(when, _run, callback, *args, context=context)

    loop.call_soon = call_soon
    loop.call_at = call_at
    loop.call_soon_threadsafe = call_soon_threadsafe
    names = ["call_soon", "call_at", "call_soon_threadsafe"]
    if hasattr(loop, "_add_reader"):
        # selector loops only (proactor has no fd readers). The Handle
        # captures its context at REGISTRATION, so registering inside a
        # context with LOOP_CATEGORY already set to "pump" labels every
        # run of the IO callback without per-run work.
        pump_ctx = contextvars.Context()
        pump_ctx.run(LOOP_CATEGORY.set, "pump")

        def _add_reader(fd, callback, *args, _orig=loop._add_reader,
                        _run=run_cb, _ctx=pump_ctx):
            return _ctx.run(_orig, fd, _run, callback, *args)

        def _add_writer(fd, callback, *args, _orig=loop._add_writer,
                        _run=run_cb, _ctx=pump_ctx):
            return _ctx.run(_orig, fd, _run, callback, *args)

        loop._add_reader = _add_reader
        loop._add_writer = _add_writer
        names += ["_add_reader", "_add_writer"]
    _wrap_waiting(loop, run_cb)
    _loop_profilers[loop] = [1, prof, tuple(names)]
    if asyncio._get_running_loop() is loop:
        bind_loop_account(prof)
        prof.adopt()
    log.info("loop profiler installed (window=%.2fs, ring=%d)", window, ring)
    return prof


def _wrap_waiting(loop, run_cb) -> None:
    """Wrap what was scheduled or registered before the hooks went in:
    the handles waiting in the ready queue and among the timers, and the
    selector's reader and writer handles (the self-pipe that wakes the
    loop for ``call_soon_threadsafe``, a server's accept) — each would
    otherwise run unseen and its time be booked as idle."""
    handles = [*getattr(loop, "_ready", ()), *getattr(loop, "_scheduled", ())]
    selector = getattr(loop, "_selector", None)
    if selector is not None:
        for key in selector.get_map().values():
            handles.extend(h for h in key.data if h is not None)
    for h in handles:
        if not h._cancelled and h._callback is not run_cb:
            h._callback, h._args = run_cb, (h._callback, *h._args)


def uninstall_loop_profiler(loop) -> None:
    """Drop one reference; the last removes the instance-attribute hooks
    (class methods take over again), folds what the account still holds
    and closes the profiler so already-wrapped callbacks pass straight
    through."""
    ent = _loop_profilers.get(loop)
    if ent is None:
        return
    ent[0] -= 1
    if ent[0] > 0:
        return
    del _loop_profilers[loop]
    _, prof, names = ent
    if prof.stats is not None:
        prof._flush()
        prof._fold()
    if asyncio._get_running_loop() is loop:
        bind_loop_account(None, prof)
    prof.closed = True
    for name in names:
        try:
            delattr(loop, name)
        except AttributeError:
            pass


class Profiler:
    """Start/stop XLA trace capture (jax.profiler.start_trace). One active
    capture per process; ``stop()`` is idempotent."""

    def __init__(self) -> None:
        self.active_dir: str | None = None

    def start(self, log_dir: str) -> None:
        if self.active_dir is not None:
            raise RuntimeError(f"trace already active → {self.active_dir}")
        jax.profiler.start_trace(log_dir)
        self.active_dir = log_dir
        log.info("device trace capturing → %s", log_dir)

    def stop(self) -> str | None:
        if self.active_dir is None:
            return None
        jax.profiler.stop_trace()
        out, self.active_dir = self.active_dir, None
        log.info("device trace written → %s", out)
        return out

    @contextlib.contextmanager
    def capture(self, log_dir: str) -> Iterator[None]:
        self.start(log_dir)
        try:
            yield
        finally:
            self.stop()
