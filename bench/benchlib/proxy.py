"""A thin proxy of the inference engine that stamps each request on the
server's own clock, and the profiler window of a traced run.

`ICCServer` advances `now` by the measured duration of each `submit`
(prefill) and `step` (decode), and jumps over idle time. It reads the
engine only through `free_slots`, `submit`, `step`, `n_active` and
`active_uids`; the proxy delegates every call and attribute. After a
`submit` or a `step` the server adds that call's duration to `now` before
it next touches the engine, so the proxy stamps the tokens the call made
at its next entry, on the clock the server has then.

Per request it records admission (`now` when `submit` is entered), the
time of each token, and so first token and completion. Per call it records
the host seconds of the call, and the prompt length or the positions of the
slots stepped, from which the configuration's reference module gives
FLOPs and bytes.
In a traced run it wraps each call in a `jax.profiler.TraceAnnotation`
("prefill", "step"; the server's own "admit" and "reap" are wrapped by
`annotate_server`) and starts and stops the profiler at fixed times of the
server's clock, from calls that the server does not time.
"""

from __future__ import annotations

import contextlib
import glob
import os
import shutil
import tempfile
import time
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Tuple

import jax


class TraceWindow:
    """Profile the server-clock interval [start, stop) into a temporary
    directory; `xplane()` gives the trace file once it has stopped."""

    def __init__(self, start: float, stop: float):
        self.start, self.stop = start, stop
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.active = False
        self.done = False
        self.wall: Tuple[float, float] = (0.0, 0.0)

    def poll(self, now: float) -> None:
        if not self.active and not self.done and now >= self.start:
            # device and host-span events only: the Python tracer would
            # record every function call of the server loop and slow it
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.active = True
            self._t0 = time.perf_counter()
        elif self.active and now >= self.stop:
            self.close()

    def close(self) -> None:
        if self.active:
            t1 = time.perf_counter()
            jax.profiler.stop_trace()
            self.wall = (self._t0, t1)
            self.active = False
            self.done = True

    def xplane(self) -> Optional[str]:
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        return found[0] if found else None

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def _span(name: str, on: bool):
    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


class EngineProxy:
    def __init__(self, engine, n_input: Dict[int, int],
                 trace: Optional[TraceWindow] = None):
        self._eng = engine
        self._n_input = n_input
        self.trace = trace
        self.clock: Callable[[], float] = lambda: 0.0
        self.admitted: Dict[int, float] = {}
        self.token_times: Dict[int, List[float]] = defaultdict(list)
        # (host seconds, prompt length, inside the traced window)
        self.prefill_calls: List[Tuple[float, int, bool]] = []
        # (host seconds, positions of the slots stepped, inside the trace)
        self.decode_calls: List[Tuple[float, Tuple[int, ...], bool]] = []
        # (kind, server clock at entry, host seconds) of every call
        self.calls: List[Tuple[str, float, float]] = []
        self._pending: List[int] = []

    def __getattr__(self, name):
        return getattr(self._eng, name)

    def _stamp(self) -> None:
        if self._pending:
            now = self.clock()
            for uid in self._pending:
                self.token_times[uid].append(now)
            self._pending = []

    def _untimed(self) -> None:
        """Entry from a call the server does not time."""
        self._stamp()
        if self.trace is not None:
            self.trace.poll(self.clock())

    def _tracing(self) -> bool:
        return self.trace is not None and self.trace.active

    # -- the calls ICCServer makes ------------------------------------
    def free_slots(self):
        self._untimed()
        return self._eng.free_slots()

    def active_uids(self):
        self._untimed()
        return self._eng.active_uids()

    @property
    def n_active(self) -> int:
        self._untimed()
        return self._eng.n_active

    def submit(self, req):
        self._stamp()
        now = self.admitted[req.uid] = self.clock()
        on = self._tracing()
        t0 = time.perf_counter()
        with _span("prefill", self.trace is not None):
            slot = self._eng.submit(req)
        dt = time.perf_counter() - t0
        self.prefill_calls.append((dt, self._n_input[req.uid], on))
        self.calls.append(("prefill", now, dt))
        self._pending.append(req.uid)
        return slot

    def step(self):
        self._stamp()
        uids = self._eng.active_uids()
        positions = tuple(self._n_input[u] + len(self.token_times[u]) - 1
                          for u in uids)
        on = self._tracing()
        t0 = time.perf_counter()
        with _span("step", self.trace is not None):
            n = self._eng.step()
        dt = time.perf_counter() - t0
        self.decode_calls.append((dt, positions, on))
        self.calls.append(("step", self.clock(), dt))
        self._pending.extend(uids)
        return n

    def finish(self) -> None:
        self._stamp()
        if self.trace is not None:
            self.trace.close()


def annotate_server(srv) -> None:
    """Host spans around the server's admission and reaping (traced runs):
    instance attributes shadow the methods `run` calls."""
    for name, label in (("_admit", "admit"), ("_reap", "reap")):
        fn = getattr(srv, name)

        def wrapped(fn=fn, label=label):
            with jax.profiler.TraceAnnotation(label):
                return fn()

        setattr(srv, name, wrapped)
