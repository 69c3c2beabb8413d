"""One run of one cell: set-up, the measured window, the check, the result.

The system under test is `repro.serving.ICCServer(policy="priority")` over
an `InferenceEngine`, built as `repro.launch.serve.serve` builds them. The
benchmark makes the weights (bench/reference/<module>.py, from the seed),
the traffic (bench/benchlib/traffic.py) and the reference; from the
program it takes only the served path, its calibrated admission estimate
and its counters.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import RuntimeFlags, build_model
from repro.serving import GenRequest, ICCRequest, ICCServer, InferenceEngine
from repro.serving.calibrate import measure_service_time

from . import check, spec, traffic
from .proxy import EngineProxy, TraceWindow, annotate_server
from .record import RequestLog, RunRecord
from .tracing import load_xplane, summarize

# the traced part of a --trace 1 window, in server-clock seconds: it opens
# after the queue has filled and lasts long enough for some hundred steps
TRACE_FROM = 0.3
TRACE_SECONDS = 8.0


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_chips(n: int) -> List:
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        raise NoChip(f"needs {n} TPU chip(s); JAX found {len(devices)} "
                     f"{devices[0].platform} device(s) ({devices[0].device_kind})")
    return devices


def use_cache() -> str:
    """The program's persistent compilation cache (.jax_cache/ in the
    checkout, or JAX_COMPILATION_CACHE_DIR), holding every program however
    short its compile, so that a warm set-up compiles nothing."""
    from repro.launch.compile_cache import use_compile_cache

    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any whole number, however large."""
    a, b = np.random.SeedSequence(int(seed) % 2**64).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(a) >> 1), int(b) >> 1)


class CompileCounter:
    """While `on`: compilations, loads from the compile cache, and the
    garbage collector's pauses, all of which would stall the window."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    LOAD = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.on = False
        self.n = 0
        self.loads = 0
        self.gc_s = 0.0
        self.gc_max_s = 0.0
        self._gc_t0 = None
        jax.monitoring.register_event_duration_secs_listener(self._compiled)
        jax.monitoring.register_event_listener(self._event)
        gc.callbacks.append(self._gc)

    def _compiled(self, event: str, duration: float, **kw) -> None:
        if self.on and event == self.COMPILE:
            self.n += 1

    def _event(self, event: str, **kw) -> None:
        if self.on and event == self.LOAD:
            self.loads += 1

    def _gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            dt, self._gc_t0 = time.perf_counter() - self._gc_t0, None
            if self.on:
                self.gc_s += dt
                self.gc_max_s = max(self.gc_max_s, dt)

    def snapshot(self) -> Dict[str, float]:
        return {"compiles_in_window": self.n, "cache_loads_in_window": self.loads,
                "gc_s": self.gc_s}

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._compiled)
        jax.monitoring.unregister_event_listener(self._event)
        gc.callbacks.remove(self._gc)


def program_config(conf: dict):
    """The registered config with the file's overrides, held by the
    configuration's reference module against the sizes the file states."""
    cfg = dataclasses.replace(get_config(conf["registered"]), **conf["overrides"])
    ref = spec.reference_module(conf["reference"])
    diff = ref.program_mismatch(cfg, conf["model"])
    if diff:
        raise spec.SpecError(f"{conf['name']}: the program runs {cfg.name} "
                             f"otherwise than the file states: {diff}")
    return cfg


def _check_layout(model, params: dict) -> None:
    want = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got != want:
        raise spec.SpecError("the benchmark's weights do not match the "
                             "program's parameter layout")


@dataclasses.dataclass
class Setup:
    cell: spec.Cell
    seed: int
    params: dict
    ref: object  # the reference module
    engine: Optional[InferenceEngine]
    est_latency: float
    phases: Dict[str, float]


def set_up(cell: spec.Cell, seed: int) -> Setup:
    """Weights from the seed, calibration and warm-up at the cell's sizes."""
    phases = {}
    t = time.perf_counter()
    conf, wl, mix = cell.config, cell.workload, cell.traffic
    cfg = program_config(conf)
    model = build_model(cfg, RuntimeFlags(remat=False))
    ref = spec.reference_module(conf["reference"])
    params = jax.block_until_ready(ref.make_params(key_from_seed(seed), conf["model"]))
    _check_layout(model, params)
    phases["weights_s"] = time.perf_counter() - t

    t = time.perf_counter()
    n_in, n_out = int(mix["n_input"]), int(mix["n_output"])
    cal = measure_service_time(model, params, n_in, n_out, max_seq=wl["max_seq"])
    phases["calibrate_s"] = time.perf_counter() - t

    t = time.perf_counter()
    eng = InferenceEngine(model, params, max_batch=wl["max_batch"],
                          max_seq=wl["max_seq"])
    prompt = jnp.asarray(np.random.default_rng(0).integers(
        0, conf["model"]["vocab_size"], n_in, dtype=np.int32))
    # every slot is filled, and one is filled again after a finish
    eng.generate([GenRequest(uid=-1 - i, prompt=prompt, max_new_tokens=3)
                  for i in range(wl["max_batch"] + 1)])
    eng.reset()
    jax.block_until_ready(eng._cache)
    phases["warmup_s"] = time.perf_counter() - t
    return Setup(cell, seed, params, ref, eng, cal["total_s"], phases)


@dataclasses.dataclass
class Window:
    requests: List[traffic.Request]
    proxy: EngineProxy
    stats: object  # ServeStats
    tokens: Dict[int, List[int]]  # uid -> served tokens
    prefill_s: Dict[int, float]  # uid -> GenResult.prefill_s
    wall_s: float
    server_s: float  # the server's clock when the last request finished
    events: Dict[str, float]  # CompileCounter's counts over the window

    @property
    def compiles(self) -> int:
        return int(self.events["compiles_in_window"])

    def diagnostics(self) -> dict:
        """What a slow window would show: host time outside the engine's
        calls, the longest calls and when they came, and stalls."""
        calls = self.proxy.calls
        longest = sorted(calls, key=lambda c: -c[2])[:3]
        return {"wall_s": self.wall_s, "server_s": self.server_s,
                "calls_s": sum(c[2] for c in calls), "n_calls": len(calls),
                "longest_calls": [list(c) for c in longest], **self.events}


def make_requests(setup: Setup, seconds: float, seed: int,
                  mix: Optional[dict] = None
                  ) -> Tuple[List[traffic.Request], List[ICCRequest]]:
    """The window's requests, and the same as the server takes them with
    their prompts on the device."""
    mix = mix or setup.cell.traffic
    reqs = traffic.generate(mix, seed, seconds, setup.cell.config["model"]["vocab_size"])
    icc = [ICCRequest(GenRequest(uid=r.uid, prompt=jnp.asarray(r.prompt),
                                 max_new_tokens=r.n_output),
                      t_gen=r.t_gen, t_comm=r.t_comm, b_total=r.b_total)
           for r in reqs]
    jax.block_until_ready([r.req.prompt for r in icc])
    return reqs, icc


def serve_window(setup: Setup, reqs, icc, counter: CompileCounter,
                 trace: Optional[TraceWindow] = None) -> Window:
    eng = setup.engine
    eng.reset()
    proxy = EngineProxy(eng, {r.uid: len(r.prompt) for r in reqs}, trace)
    srv = ICCServer(proxy, policy="priority", est_latency=setup.est_latency)
    proxy.clock = lambda: srv.now
    if trace is not None:
        annotate_server(srv)
    before = counter.snapshot()
    counter.gc_max_s = 0.0
    counter.on = True
    t0 = time.perf_counter()
    try:
        stats = srv.run(icc)
        proxy.finish()
    finally:
        counter.on = False
    wall = time.perf_counter() - t0
    events = {k: v - before[k] for k, v in counter.snapshot().items()}
    events["gc_max_s"] = counter.gc_max_s
    tokens = {u: list(r.tokens) for u, r in eng.results.items()}
    pre = {u: r.prefill_s for u, r in eng.results.items()}
    return Window(reqs, proxy, stats, tokens, pre, wall, srv.now, events)


def record(setup: Setup, win: Window, seconds: float, setup_s: float,
           peaks: dict, trace_summary=None) -> RunRecord:
    logs = []
    for r in win.requests:
        logs.append(RequestLog(
            uid=r.uid, t_gen=r.t_gen, arrival=r.arrival, b_total=r.b_total,
            n_output=r.n_output, admitted=win.proxy.admitted.get(r.uid),
            token_times=list(win.proxy.token_times.get(r.uid, []))))
    served = [l for l in logs if l.served]
    return RunRecord(
        cell=setup.cell.name, seconds=seconds, model=setup.cell.config["model"],
        ref=setup.ref, peaks=peaks, setup_s=setup_s, sent=len(logs),
        dropped=win.stats.n_dropped, requests=logs,
        prefill_calls=list(win.proxy.prefill_calls),
        decode_calls=list(win.proxy.decode_calls),
        prefill_s_program=[win.prefill_s[l.uid] for l in served],
        trace=trace_summary)


def malformed(win: Window, vocab: int) -> int:
    """Requests sent that errored or came back malformed: neither served
    whole nor dropped by admission, a wrong token count, or a token
    outside the vocabulary."""
    bad = 0
    for r in win.requests:
        toks = win.tokens.get(r.uid)
        if toks is None:
            continue
        if len(toks) != r.n_output or not all(0 <= t < vocab for t in toks):
            bad += 1
    missing = len(win.requests) - len(win.tokens) - win.stats.n_dropped
    return bad + max(missing, 0)


def sample_rows(setup: Setup, win: Window, seed: int) -> List[check.Row]:
    wl = setup.cell.workload
    vocab = setup.cell.config["model"]["vocab_size"]
    ok = {r.uid: r for r in win.requests
          if r.uid in win.tokens and len(win.tokens[r.uid]) == r.n_output
          and all(0 <= t < vocab for t in win.tokens[r.uid])}
    uids = check.sample_uids({u: len(r.prompt) + r.n_output for u, r in ok.items()},
                             wl["check"]["sample_requests"], seed)
    return [(ok[u].prompt, win.tokens[u]) for u in uids]


def free_engine(setup: Setup) -> None:
    """Drop the engine and its cache so the reference has the memory."""
    setup.engine = None
    gc.collect()


def memory_peak(devices) -> Optional[int]:
    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    vals = [v for v in vals if v is not None]
    return int(max(vals)) if vals else None


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             t_start: float, require: Callable = require_chips,
             peaks: Optional[dict] = None,
             fault: Optional[Callable] = None) -> dict:
    """One run. `require` checks the devices and `peaks` stands in for the
    table (tests, off the chip); `fault` (tests) may break the engine
    before the window. Returns the result object."""
    devices = require(cell.chips)
    dev0 = devices[0]
    peaks = peaks or spec.peaks(dev0.device_kind)
    counter = CompileCounter()
    setup = set_up(cell, seed)
    if fault is not None:
        fault(setup)
    reqs, icc = make_requests(setup, seconds, seed)
    tw = TraceWindow(TRACE_FROM * seconds,
                     TRACE_FROM * seconds + min(TRACE_SECONDS, 0.5 * seconds)) \
        if traced else None
    setup_s = time.perf_counter() - t_start
    print(json.dumps({"setup": {"setup_s": setup_s, **setup.phases}}), flush=True)

    win = serve_window(setup, reqs, icc, counter, tw)
    mem = memory_peak(devices)
    print(json.dumps({"window": win.diagnostics()}), flush=True)
    summary = None
    if tw is not None:
        path = tw.xplane()
        if path and tw.done:
            summary = summarize(load_xplane(path), tw.wall[1] - tw.wall[0])
        tw.cleanup()
    rec = record(setup, win, seconds, setup_s, peaks, summary)
    vocab = cell.config["model"]["vocab_size"]
    failed = malformed(win, vocab)

    free_engine(setup)
    rows = sample_rows(setup, win, seed)
    lim = cell.workload["check"]
    reading = check.widest_gap(setup.ref, setup.params, cell.config["model"],
                               rows, lim["ref_batch"])
    checks = {
        "max_logit_gap": {"value": reading.max_gap, "limit": lim["max_logit_gap"]},
        "failed_requests": {"value": failed, "limit": 0},
        "compared_tokens": {"value": reading.tokens,
                            "limit": lim["min_compared_tokens"]},
    }
    correct = bool(reading.tokens >= lim["min_compared_tokens"]
                   and reading.max_gap <= lim["max_logit_gap"]
                   and failed == 0)

    metrics = {}
    for ms in cell.per_layer if traced else cell.end_to_end:
        val = spec.metric_module(ms.name).read(rec)
        if val is not None and np.isfinite(val):
            metrics[ms.name] = {"value": float(val), "unit": ms.unit}
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": len(reqs), "failed": failed,
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": [list(x) for x in summary.device_ops],
                            "idle_gaps": [list(x) for x in summary.gaps]}
    out["checks"] = checks
    counter.close()
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return out
