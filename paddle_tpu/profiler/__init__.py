"""paddle.profiler parity over the jax/XLA profiler.

Reference: python/paddle/profiler/profiler.py:340 (Profiler with scheduler
states :79, chrome-trace export, summary tables in profiler_statistic.py);
RecordEvent hooks are generated into every ad_func (eager_gen.py template).

TPU mapping: device-side tracing is jax.profiler (XPlane → TensorBoard/
Perfetto); host-side op events are collected by ``RecordEvent`` (wired into
eager dispatch when a profiler is active) and aggregated into the reference's
summary-table shape.
"""

import contextlib
import json
import os
import threading
import time
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
    """How many executables this process has compiled or loaded from the
    persistent cache so far: jax reports each to its monitoring
    listeners.  One listener for the process, registered by the first
    ``StepTrace`` (jax offers no public way to take one away again)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    count = 0
    _listening = False

    @classmethod
    def listen(cls):
        if not cls._listening:
            cls._listening = True
            jax.monitoring.register_event_duration_secs_listener(cls._on)

    @classmethod
    def _on(cls, event, _seconds, **_kw):
        if event == cls.EVENT:
            cls.count += 1


class StepTrace:
    """The host spans and the compile count of a compiled train step:
    ``jit.TrainStep`` and ``parallel.SpmdTrainStep`` mark every call the
    same way, so one reader of the trace serves both (docs/PROFILER.md).

    ``train_step`` covers the whole call; inside it ``::operands`` (RNG
    key, step and learning-rate scalars, the batch's placement),
    ``::dispatch`` (the call of the compiled step and nothing else) and,
    where the trainer rebinds its model, ``::sync_to_model``.  A dispatch
    that added an executable to the step's cache is followed by the
    zero-length marker ``::compiled``.  Every span carries ``step``."""

    STEP = "train_step"
    OPERANDS = "train_step::operands"
    DISPATCH = "train_step::dispatch"
    SYNC = "train_step::sync_to_model"
    COMPILED = "train_step::compiled"

    def __init__(self):
        self.compiles = 0
        _ExecutablesBuilt.listen()

    def dispatch(self, compiled, args, step):
        """``compiled(*args)`` under its span; counts and marks the call
        if it compiled: the step's cache gained an entry
        (``_cache_size``, as ``CompileWatcher`` counts) AND an executable
        was built meanwhile.  The cache alone also grows when operands
        merely come back described differently (``SpmdTrainStep``'s second
        call: its outputs drop the mesh axes of size 1 from their specs),
        which builds nothing."""
        known, built = compiled._cache_size(), _ExecutablesBuilt.count
        with RecordEvent(self.DISPATCH, step=step):
            out = compiled(*args)
        if compiled._cache_size() > known and _ExecutablesBuilt.count > built:
            self.compiles += 1
            with RecordEvent(self.COMPILED, step=step):
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
        except Exception:
            self._device_tracing = False

    def _stop_device_trace(self):
        if self._device_tracing:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._device_tracing = False

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
