"""paddle.profiler parity over the jax/XLA profiler.

Reference: python/paddle/profiler/profiler.py:340 (Profiler with scheduler
states :79, chrome-trace export, summary tables in profiler_statistic.py);
RecordEvent hooks are generated into every ad_func (eager_gen.py template).

TPU mapping: device-side tracing is jax.profiler (XPlane → TensorBoard/
Perfetto); host-side op events are collected by ``RecordEvent`` (wired into
eager dispatch when a profiler is active) and aggregated into the reference's
summary-table shape.
"""

import collections
import contextlib
import functools
import gc
import json
import os
import resource
import statistics
import threading
import time
import warnings
from enum import Enum

import jax


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    CUSTOM_DEVICE = 2
    TPU = 3


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(*, closed=0, ready=0, record=1, repeat=0, skip_first=0):
    """Reference profiler.make_scheduler: step -> ProfilerState."""
    cycle = closed + ready + record
    if cycle <= 0:
        raise ValueError("scheduler cycle must be positive")

    def schedule(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


class _HostEvents(threading.local):
    def __init__(self):
        self.active = False
        self.records = []   # (name, start, dur)
        self.stack = []


_events = _HostEvents()


class RecordEvent:
    """Host event span (reference platform/profiler RecordEvent); also
    emits a jax TraceAnnotation so spans appear in the XLA timeline, on
    the device trace's clock.  ``attrs`` (``step=7``) become the
    annotation's keywords: the trace shows them on the span.

    While nothing records, a span costs the one TraceAnnotation (it asks
    the profiler whether a session is on and formats nothing) and this
    wrapper's few attribute reads; what the annotation raises is raised, a
    span never silently vanishes."""

    def __init__(self, name, event_type=None, **attrs):
        self.name = name
        self._attrs = attrs
        self._ann = None
        self._t0 = None

    def begin(self):
        if _events.active:
            self._t0 = time.perf_counter()
        self._ann = ann = jax.profiler.TraceAnnotation(self.name,
                                                       **self._attrs)
        ann.__enter__()

    def end(self):
        ann = self._ann
        if ann is not None:
            self._ann = None
            ann.__exit__(None, None, None)
        if self._t0 is not None:
            if _events.active:
                _events.records.append(
                    (self.name, self._t0, time.perf_counter() - self._t0))
            self._t0 = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


class _ExecutablesBuilt:
    """The process's compile log: one small record for every executable
    this process has compiled or loaded from the persistent cache.  jax
    reports each to its monitoring listeners, the seconds as duration
    events and the cache's answer as plain ones; this class is the ONE
    listener of both kinds, registered when this module is imported (jax
    offers no public way to take one away again) and run only when
    something compiles.

    A record is closed by the backend's event: ``at`` (``perf_counter``
    when it came), ``since`` (when its tracing began), ``program`` (jax's
    name, ``jit(train_step)``), ``trace_s`` and ``lower_s`` (the program's
    own trace, which holds those of the jits it calls, and its lowering,
    which holds what its rules trace), ``backend_s`` (the compile, or the
    cache's retrieval where there was one) and ``cache``: ``hit``, ``miss``, or
    ``off`` where no persistent cache was asked.  ``count`` is the number
    of records ever closed; ``log`` keeps the newest ``KEEP``."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    EVENT = "/jax/core/compile/backend_compile_duration"
    CACHE = {"/jax/compilation_cache/cache_hits": "hit",
             "/jax/compilation_cache/cache_misses": "miss"}
    KEEP = 4096
    count = 0
    log = collections.deque(maxlen=KEEP)
    _lock = threading.Lock()

    class _Open(threading.local):
        """What this thread's next record has gathered so far (one thread
        traces, lowers and compiles a program), and its last record."""

        NONE = (float("inf"), 0.0)      # (start, seconds) of no event
        last = None

        def __init__(self):
            self.reset()

        def reset(self):
            self.traces = []        # (start, seconds), none inside another
            self.trace = self.lower = self.NONE     # the program's own
            self.cache = "off"

    _open = _Open()

    @classmethod
    def _on_cache(cls, event, **_kw):
        if event in cls.CACHE:
            cls._open.cache = cls.CACHE[event]

    @classmethod
    def _on(cls, event, seconds, fun_name="", **_kw):
        mine, now = cls._open, time.perf_counter()
        start = now - seconds
        if event == cls.TRACE:
            # every jit called while another is traced reports its own
            # trace first: the one that holds them replaces them
            while mine.traces and mine.traces[-1][0] >= start:
                mine.traces.pop()
            mine.traces.append((start, seconds))
        elif event == cls.LOWER:
            # the program's trace is the last that began before its
            # lowering did; what a lowering rule traced is in ``lower_s``
            own = [t for t in mine.traces if t[0] < start]
            mine.trace = own[-1] if own else mine.NONE
            mine.lower = (start, seconds)
            mine.traces = []
        elif event == cls.EVENT:
            cls.add({"kind": "executable", "program": fun_name, "at": now,
                     "since": min(mine.trace[0], mine.lower[0], start),
                     "trace_s": mine.trace[1], "lower_s": mine.lower[1],
                     "backend_s": seconds, "cache": mine.cache})
            mine.reset()

    @classmethod
    def add(cls, record):
        with cls._lock:
            cls.log.append(record)
            if record["kind"] == "executable":
                cls.count += 1
                cls._open.last = record

    @classmethod
    def account(cls, compiled, args, step):
        """The record of the executable the call ``compiled(*args)`` just
        built, with the step's account attached: ``name``, ``step``,
        ``call_s`` (from the start of its tracing to the call's return,
        now), the compiler's memory account of that executable on ONE
        chip, ``argument`` / ``output`` / ``alias`` / ``temp`` /
        ``generated_code`` ``_bytes`` and ``reserved_bytes`` = argument +
        output - alias + temp + code, ``collectives_async`` /
        ``collectives_sync`` (``parallel.compiled_collectives`` over the
        executable's text), and ``account_s``: what taking this account
        cost.

        The executable is reached, never built again: lowering the jitted
        step for the abstract operands of that very call (donated buffers
        keep their shape, dtype and sharding) is answered from jax's
        caches, with no backend event."""
        began = time.perf_counter()
        record, built = cls._open.last, cls.count
        record.update(name=StepTrace.STEP, step=step,
                      call_s=began - record["since"])

        def described(a):
            if not isinstance(a, jax.Array):
                return a
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype, weak_type=a.weak_type,
                sharding=a.sharding if a.committed else None)

        executable = compiled.lower(
            *jax.tree_util.tree_map(described, args)).compile()
        memory = executable.memory_analysis()
        cls._open.reset()       # the lowering's own (cached) trace event
        if cls.count != built:
            warnings.warn(
                "taking the compiled step's account built an executable a "
                "second time: the operands of the call no longer describe "
                "the program jax cached for it", RuntimeWarning)
        if memory is not None:      # a backend that keeps no account
            sizes = {k: int(getattr(memory, f"{k}_size_in_bytes"))
                     for k in ("argument", "output", "alias", "temp",
                               "generated_code")}
            record.update({f"{k}_bytes": v for k, v in sizes.items()})
            record["reserved_bytes"] = (
                sizes["argument"] + sizes["output"] - sizes["alias"]
                + sizes["temp"] + sizes["generated_code"])
        # the collectives IN THE COMPILED TEXT (a scan's body stands once):
        # how many the compiler made asynchronous, how many run in line.
        # A program over one device holds none: its text is not read
        from ..parallel.trainer import compiled_collectives

        meshed = any(isinstance(a, jax.Array)
                     and len(a.sharding.device_set) > 1
                     for a in jax.tree_util.tree_leaves(args))
        found = compiled_collectives(executable.as_text()) if meshed else []
        record["collectives_async"] = sum(1 for c in found if c[2])
        record["collectives_sync"] = len(found) - record["collectives_async"]
        record["account_s"] = time.perf_counter() - began
        return record


jax.monitoring.register_event_duration_secs_listener(_ExecutablesBuilt._on)
jax.monitoring.register_event_listener(_ExecutablesBuilt._on_cache)


def compile_log():
    """The records of the process's compile log, oldest first: every
    executable built or loaded (``kind`` ``executable``; a train step's
    carries its account, ``_ExecutablesBuilt.account``) and every train
    step object's construction (``kind`` ``init``: ``name``, ``at``,
    ``seconds``).  docs/PROFILER.md, "The compile's account"."""
    return list(_ExecutablesBuilt.log)


def step_log():
    """The process's flight record of its train steps, oldest first: one
    small record for every call of a ``jit.TrainStep`` or a
    ``parallel.SpmdTrainStep`` (the newest ``StepTrace.KEEP``), kept with
    or without a profiler session, and after the step object has gone.
    docs/PROFILER.md, "The step's flight record"."""
    with StepTrace._lock:
        return list(StepTrace.log)


class _Collections:
    """Python's collector, counted: ``count`` collections that took
    ``seconds`` in all since the process began.  ONE function in
    ``gc.callbacks``, registered when this module is imported; it runs only
    when a collection does."""

    count = 0
    seconds = 0.0
    _began = None

    @classmethod
    def _on(cls, phase, info):
        if phase == "start":
            cls._began = time.perf_counter()
        elif cls._began is not None:
            cls.count += 1
            cls.seconds += time.perf_counter() - cls._began
            cls._began = None


gc.callbacks.append(_Collections._on)


class _SchedStat:
    """The calling thread's ``/proc/thread-self/schedstat``, kept open: the
    name is resolved when the file is opened, so the descriptor stays this
    thread's and is read by it alone (a read costs 0.6 us, opening the
    file each time 8).  ``fd`` is ``None`` where there is no such file.
    Closed when the thread's locals go."""

    def __init__(self):
        try:
            self.fd = os.open(StepTrace.SCHEDSTAT, os.O_RDONLY)
        except OSError:
            self.fd = None

    def close(self):
        fd, self.fd = self.fd, None
        if fd is not None:
            with contextlib.suppress(OSError):
                os.close(fd)

    __del__ = close


_sched = threading.local()      # .stat: this thread's _SchedStat


def _run_delay_ns():
    """The nanoseconds the calling thread has stood RUNNABLE without a CPU
    (the second number of its ``schedstat``), or ``None``."""
    try:
        stat = _sched.stat
    except AttributeError:
        stat = _sched.stat = _SchedStat()
    if stat.fd is None:
        return None
    try:
        return int(os.pread(stat.fd, 64, 0).split()[1])
    except (OSError, ValueError, IndexError):
        # a descriptor from before a fork, a short read: the record goes
        # on without run-delay, and never raises into the step
        stat.close()
        return None


def thread_snapshot():
    """``[(tid, comm, on-CPU ns, run-delay ns, timeslices)]`` of every task
    of the process, cumulative (``/proc/self/task/<tid>/schedstat``): the
    difference of two snapshots says which threads ran, which stood
    runnable without a CPU and which never woke.  ``None`` where the
    calling thread has no ``schedstat`` to read; a thread that ends while
    the tasks are listed is left out."""
    if _run_delay_ns() is None:
        return None
    out = []
    for tid in os.listdir(StepTrace.TASKS):
        try:
            with open(f"{StepTrace.TASKS}/{tid}/comm") as f:
                comm = f.read().strip()
            with open(f"{StepTrace.TASKS}/{tid}/schedstat") as f:
                on_cpu, delay, slices = map(int, f.read().split())
        except OSError:
            continue
        out.append((int(tid), comm, on_cpu, delay, slices))
    return out


class _Phase:
    """A span of the step that also writes its seconds into the call's
    record."""

    __slots__ = ("record", "field", "span", "t0")

    def __init__(self, record, name):
        self.record, self.field = record, StepTrace.PHASES[name]
        self.span = RecordEvent(name, step=record["step"])

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.span.begin()

    def __exit__(self, *exc):
        self.span.end()
        self.record[self.field] = time.perf_counter() - self.t0


class StepTrace:
    """The host spans, the compile count, the compile's account and the
    flight record of a compiled train step: ``jit.TrainStep`` and
    ``parallel.SpmdTrainStep`` mark every call the same way, so one reader
    of the trace serves both (docs/PROFILER.md).

    ``train_step`` covers the whole call; inside it ``::operands`` (RNG
    key, step and learning-rate scalars, the batch's placement),
    ``::dispatch`` (the call of the compiled step and nothing else) and,
    where the trainer rebinds its model, ``::sync_to_model``.  A dispatch
    that added an executable to the step's cache is followed by the
    zero-length marker ``::compiled``, which says what the compile was
    (``cache``, ``backend_s``, ``temp_bytes``); ``account`` is that
    call's record of the compile log, the newest, with the step's
    ``flash_calls``, ``flash_operands_in_place`` and
    ``flash_operands_copied`` (``ops.pallas.record_flash_layout``), its
    ``ssd_calls`` / ``ssd_calls_composed`` (``ops.pallas.ssd_scan``), the
    compiled text's ``collectives_async`` / ``collectives_sync`` and
    ``remat_kept``: what the trainer's ``remat`` keeps of a rematerialised
    block (``fleet.recompute.remat_kept``: the tags, a policy's name, or
    ``None`` where nothing is rematerialised).  Every
    span carries ``step``.  ``::init`` covers the trainer's construction.

    The flight record (docs/PROFILER.md, "The step's flight record") is
    taken where those spans open and close, with or without a profiler
    session: ``with trace.call(step):`` round the call, ``with
    trace.phase(name):`` round a phase.  Every call leaves one small
    record in ``log`` (the process's, read by ``profiler.step_log()``), the
    newest ``KEEP``.  At the entry of call n+1 record n is marked ``long``
    where ``enter[n+1] - enter[n]`` passed ``LONG`` times the median of the
    last ``MEDIAN_OF`` such intervals (``MEDIAN_MIN`` known at least, none
    of a call that compiled); ``long_steps`` counts them."""

    STEP = "train_step"
    OPERANDS = "train_step::operands"
    DISPATCH = "train_step::dispatch"
    SYNC = "train_step::sync_to_model"
    COMPILED = "train_step::compiled"
    INIT = "train_step::init"
    PHASES = {OPERANDS: "operands_s", DISPATCH: "dispatch_s",
              SYNC: "sync_s"}

    SCHEDSTAT = "/proc/thread-self/schedstat"
    TASKS = "/proc/self/task"
    KEEP = 4096
    LONG = 1.25
    MEDIAN_OF = 16
    MEDIAN_MIN = 8
    SNAPSHOT_OVER = 0.02    # seconds over the median that are worth the
    #                         threads' snapshot (it costs 1-2 ms)
    log = collections.deque(maxlen=KEEP)
    _lock = threading.Lock()

    def __init__(self, remat_kept=None):
        self.compiles = 0
        self.account = None
        self.remat_kept = remat_kept    # the trainer's, for the account
        self.long_steps = 0
        self._intervals = collections.deque(maxlen=self.MEDIAN_OF)
        self._record = {}       # the newest call's
        self._last = None       # its probes, at its entry and its return

    @staticmethod
    def init(constructor):
        """Decorates a trainer's ``__init__``: the whole of it (state
        split, optimizer state, placement) runs under the span ``::init``,
        and its seconds go into the compile log as a record of kind
        ``init``, read whether or not a profiler session is on."""
        @functools.wraps(constructor)
        def timed(self, *args, **kwargs):
            t0 = time.perf_counter()
            with RecordEvent(StepTrace.INIT):
                constructor(self, *args, **kwargs)
            now = time.perf_counter()
            _ExecutablesBuilt.add({"kind": "init", "name": StepTrace.INIT,
                                   "at": now, "seconds": now - t0})
        return timed

    @contextlib.contextmanager
    def call(self, step):
        """``with trace.call(step):`` round one call of the step: its
        record and, inside the record's probes, the span ``train_step``.
        The clock is read first at the entry and last at the return, so
        what the record costs lies inside ``call_s`` and never in
        ``between_s``; the span opens after the entry's probes and closes
        before the return's, so it covers what it covered without them."""
        now = time.perf_counter()
        ident, cpu, delay = (threading.get_ident(), time.thread_time_ns(),
                             _run_delay_ns())
        process = time.process_time_ns()
        usage = resource.getrusage(resource.RUSAGE_THREAD)
        collections_, collecting = _Collections.count, _Collections.seconds
        self._record = rec = {
            "name": self.STEP, "step": step, "enter": now, "call_s": None,
            "operands_s": 0.0, "dispatch_s": 0.0, "sync_s": 0.0,
            "between_s": None, "between_cpu_s": None, "call_cpu_s": None,
            "between_run_delay_s": None, "call_run_delay_s": None,
            "process_cpu_s": None, "nivcsw": None, "majflt": None,
            "gc": None, "compiled": False, "long": False, "threads": None}
        last = self._last
        # CPU seconds and context switches are a thread's own: a call by
        # another thread than the last starts anew, as an object's first
        if last is not None and last["ident"] == ident:
            rec["between_s"] = now - last["left"]
            rec["between_cpu_s"] = 1e-9 * (cpu - last["left_cpu"])
            if None not in (delay, last["left_delay"]):
                rec["between_run_delay_s"] = 1e-9 * (
                    delay - last["left_delay"])
            rec["process_cpu_s"] = 1e-9 * (process - last["process"])
            rec["nivcsw"] = usage.ru_nivcsw - last["usage"].ru_nivcsw
            rec["majflt"] = usage.ru_majflt - last["usage"].ru_majflt
            rec["gc"] = (collections_ - last["collections"],
                         collecting - last["collecting"])
            self._judge(last["record"], now - last["record"]["enter"], rec)
        self._last = last = {
            "ident": ident, "record": rec, "process": process,
            "usage": usage, "collections": collections_,
            "collecting": collecting}
        try:
            with RecordEvent(self.STEP, step=step):
                yield
        finally:
            with self._lock:
                self.log.append(rec)
            left_cpu, left_delay = time.thread_time_ns(), _run_delay_ns()
            rec["call_cpu_s"] = 1e-9 * (left_cpu - cpu)
            if None not in (delay, left_delay):
                rec["call_run_delay_s"] = 1e-9 * (left_delay - delay)
            last["left_cpu"], last["left_delay"] = left_cpu, left_delay
            last["left"] = left = time.perf_counter()
            rec["call_s"] = left - now

    def phase(self, name):
        """``with trace.phase(trace.OPERANDS):`` inside ``call``: the span
        of that name, its seconds into the call's record."""
        return _Phase(self._record, name)

    def _judge(self, before, interval, rec):
        """At the entry of a call: was the interval since the entry of the
        call ``before`` a long one?  A call that compiled is never long
        and never in the median; the first call after one takes the
        baseline snapshot of the threads."""
        if before["compiled"]:
            rec["threads"] = thread_snapshot()
            return
        if len(self._intervals) >= self.MEDIAN_MIN:
            median = statistics.median(self._intervals)
            if interval > self.LONG * median:
                before["long"] = True
                self.long_steps += 1
                if interval - median > self.SNAPSHOT_OVER:
                    before["threads"] = thread_snapshot()
        self._intervals.append(interval)

    def dispatch(self, compiled, args, step):
        """``compiled(*args)`` under its span; counts and marks the call
        if it compiled: the step's cache gained an entry
        (``_cache_size``, as ``CompileWatcher`` counts) AND an executable
        was built meanwhile.  The cache alone also grows when operands
        merely come back described differently (``SpmdTrainStep``'s second
        call: its outputs drop the mesh axes of size 1 from their specs),
        which builds nothing.  Only a call that compiled takes the
        account; any other does what it always did, but for reading the
        dispatchers' sums (the flash layout record's three, the scan's
        two) beforehand (what was traced DURING the call is the step's
        own)."""
        from ..ops.pallas import traced_call_sums

        known, built = compiled._cache_size(), _ExecutablesBuilt.count
        traced = traced_call_sums()
        with self.phase(self.DISPATCH):
            out = compiled(*args)
        if compiled._cache_size() > known and _ExecutablesBuilt.count > built:
            self.compiles += 1
            self._record["compiled"] = True
            self.account = rec = _ExecutablesBuilt.account(compiled, args,
                                                           step)
            # the flash calls traced while the step was built are the
            # step's own: how many of their operands cross between XLA
            # and the kernels in place, how many as copies; and its
            # state-space scans, and those the composition served
            rec.update({k: n - traced[k]
                        for k, n in traced_call_sums().items()})
            rec["remat_kept"] = self.remat_kept
            with RecordEvent(self.COMPILED, step=step, cache=rec["cache"],
                             backend_s=rec["backend_s"],
                             temp_bytes=rec.get("temp_bytes")):
                pass
        return out


def record_host_event(name, start, dur):
    if _events.active:
        _events.records.append((name, start, dur))


def host_events_active():
    return _events.active


class Profiler:
    """paddle.profiler.Profiler API shape.

    >>> p = Profiler(targets=[ProfilerTarget.CPU], timer_only=True)
    >>> p.start()
    ... train ...
    >>> p.step()
    >>> p.stop()
    >>> p.summary()
    """

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False, trace_dir=None):
        self.targets = targets or [ProfilerTarget.CPU]
        if isinstance(scheduler, tuple):
            start, end = scheduler
            scheduler = make_scheduler(closed=start, ready=0,
                                       record=end - start, repeat=1)
        self.scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.step_num = 0
        self._device_tracing = False
        self._trace_dir = trace_dir
        self._running = False

    # ------------------------------------------------------------ control --
    def _start_device_trace(self):
        if self.timer_only or self._device_tracing:
            return
        self._trace_dir = self._trace_dir or os.path.join(
            "/tmp", f"paddle_tpu_profile_{os.getpid()}")
        try:
            jax.profiler.start_trace(self._trace_dir)
            self._device_tracing = True
        except Exception as e:     # the host events are still recorded
            self._device_tracing = False
            warnings.warn(
                f"the device trace did not start ({type(e).__name__}: {e}): "
                f"{self._trace_dir} will hold no trace of this window",
                RuntimeWarning)

    def _stop_device_trace(self):
        if self._device_tracing:
            self._device_tracing = False
            try:
                jax.profiler.stop_trace()
            except Exception as e:
                warnings.warn(
                    f"the device trace did not stop cleanly "
                    f"({type(e).__name__}: {e}): {self._trace_dir} may hold "
                    f"no trace of this window", RuntimeWarning)

    def _apply_state(self, state):
        recording = state in (ProfilerState.RECORD,
                              ProfilerState.RECORD_AND_RETURN)
        _events.active = recording
        if recording:
            self._start_device_trace()
        else:
            self._stop_device_trace()
        if state == ProfilerState.RECORD_AND_RETURN and \
                self.on_trace_ready is not None:
            self.on_trace_ready(self)

    def start(self):
        self._running = True
        _events.records = []
        if self.scheduler is not None:
            self._apply_state(self.scheduler(self.step_num))
        else:
            _events.active = True
            self._start_device_trace()

    def stop(self):
        if not self._running:
            return
        self._stop_device_trace()
        _events.active = False
        self._running = False
        if self.on_trace_ready is not None and self.scheduler is None:
            self.on_trace_ready(self)

    def step(self, num_samples=None):
        self.step_num += 1
        if self._running and self.scheduler is not None:
            self._apply_state(self.scheduler(self.step_num))

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    # ------------------------------------------------------------ reports --
    def aggregated_events(self):
        agg = {}
        for name, _, dur in _events.records:
            tot, cnt, mx = agg.get(name, (0.0, 0, 0.0))
            agg[name] = (tot + dur, cnt + 1, max(mx, dur))
        return agg

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        """Reference summary table (profiler_statistic.py) — host op times."""
        unit = {"s": 1.0, "ms": 1e3, "us": 1e6}[time_unit]
        agg = sorted(self.aggregated_events().items(),
                     key=lambda kv: -kv[1][0])
        lines = [f"{'Name':<40} {'Calls':>8} {'Total(' + time_unit + ')':>14} "
                 f"{'Avg(' + time_unit + ')':>12} {'Max(' + time_unit + ')':>12}"]
        lines.append("-" * len(lines[0]))
        for name, (tot, cnt, mx) in agg:
            lines.append(f"{name[:40]:<40} {cnt:>8} {tot * unit:>14.4f} "
                         f"{tot / cnt * unit:>12.4f} {mx * unit:>12.4f}")
        table = "\n".join(lines)
        print(table)
        return table

    def export_chrome_tracing(self, path):
        """Write host events as a chrome://tracing JSON file (the reference's
        chrometracing_logger.cc output shape)."""
        events = []
        for name, start, dur in _events.records:
            events.append({"name": name, "ph": "X", "pid": os.getpid(),
                           "tid": 0, "ts": start * 1e6, "dur": dur * 1e6})
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump({"traceEvents": events}, f)
        return path

    def export(self, path, format="json"):
        return self.export_chrome_tracing(path)


@contextlib.contextmanager
def profiler_guard(**kwargs):
    p = Profiler(**kwargs)
    p.start()
    try:
        yield p
    finally:
        p.stop()
