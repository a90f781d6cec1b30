"""The harness: everything a run does between its arguments and its line.

Driven by data. ``BENCHMARK.json`` names a cell's configuration and traffic
mix; ``workloads/<cell>.json`` holds the cell's own parameters (rate, check
sample, limits); the configuration file names its builder and reference;
each metric is a module ``end_to_end/<name>.py`` or ``layers/<name>.py``
with one function ``read(ctx)`` and, where it cannot read every
configuration, ``requires(config)``, which raises at load. Adding a cell, a
configuration or a metric adds files and entries and edits nothing here.
"""

from __future__ import annotations

import importlib
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)

#: the traced sub-window: the last seconds of the measured window, so that
#: stopping the profiler (which stalls the host for tens of seconds at these
#: sizes) falls after its close
TRACE_SECONDS = 4.0
#: how long past the window's close a request is waited for
DRAIN_SECONDS = 60.0


# --------------------------------------------------------------------------- #
# data files
# --------------------------------------------------------------------------- #

def _load_json(path: str) -> Dict[str, Any]:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    mix: Dict[str, Any]
    params: Dict[str, Any]          # workloads/<cell>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_cell(name: str, bench_path: Optional[str] = None) -> Cell:
    from .traffic import open_loop

    bench = _load_json(bench_path or os.path.join(CHECKOUT,
                                                  "BENCHMARK.json"))
    rows = [w for w in bench["workloads"] if w["name"] == name]
    if not rows:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{[w['name'] for w in bench['workloads']]}")
    w = rows[0]
    cfg_row = next(c for c in bench["configs"] if c["name"] == w["config"])
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    per = [m for m in bench["per_layer"]
           if "workloads" not in m or name in m["workloads"]]
    config = _load_json(os.path.join(CHECKOUT, cfg_row["file"]))
    for kind, rows in (("end_to_end", e2e), ("layers", per)):
        for m in rows:
            # a reader that cannot read this configuration says so now,
            # not after the window
            requires = getattr(reader_of(kind, m["name"]), "requires", None)
            if requires is not None:
                requires(config)
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=config,
        traffic_name=w["traffic"], mix=open_loop.load_mix(w["traffic"]),
        params=_load_json(os.path.join(HERE, "workloads", f"{name}.json")),
        end_to_end=e2e, per_layer=per)


def reader_of(kind: str, name: str) -> Any:
    """The module that reads the metric ``name``. ``a.b`` is read by ``a``:
    one reader, split by the cells' end-to-end metric."""
    stem = name.split(".", 1)[0].replace("-", "_")
    return importlib.import_module(f"{__package__}.{kind}.{stem}")


# --------------------------------------------------------------------------- #
# the device
# --------------------------------------------------------------------------- #

def place_compile_cache() -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` or at the
    fixed ``<checkout>/.jax_cache``; every program is kept, however fast
    it compiled, so that a second run compiles nothing."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_chips(chips: int) -> Dict[str, Any]:
    """The devices as JAX reports them, or exit 2: no TPU, fewer chips
    than the cell asks for, or a ``device_kind`` without recorded peaks."""
    import jax

    from .peaks import peaks_for

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"benchmark: JAX found no TPU (platform "
              f"{devs[0].platform!r}); a CPU number is never reported "
              "under a device metric's name", file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < chips:
        print(f"benchmark: the cell asks for {chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        raise SystemExit(2)
    try:
        peaks_for(devs[0].device_kind)
    except KeyError as e:
        print(f"benchmark: {e.args[0]}", file=sys.stderr)
        raise SystemExit(2)
    return device_info(chips)


def device_info(chips: int) -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": min(len(devs), chips) if chips else len(devs)}


def memory_peak_bytes(chips: int) -> int:
    import jax

    peak = 0
    for d in jax.devices()[:max(chips, 1)]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# --------------------------------------------------------------------------- #
# the window
# --------------------------------------------------------------------------- #

@dataclass
class RequestRecord:
    index: int
    due_s: float
    prompt: np.ndarray
    max_new: int
    submit_s: Optional[float] = None
    handle: Any = None
    token_s: List[float] = field(default_factory=list)  # one stamp a token

    @property
    def finished(self) -> bool:
        return len(self.token_s) >= self.max_new


@dataclass
class IterationRecord:
    t0: float
    t1: float
    in_window: bool
    traced: bool
    pending_after: int
    decode_steps: int
    #: (request index, tokens before, tokens after) of every request
    #: that held a slot in this iteration
    progress: List[Any] = field(default_factory=list)


@dataclass
class WindowResult:
    seconds: float
    requests: List[RequestRecord]
    iterations: List[IterationRecord]
    stats_start: Dict[str, float]
    stats_end: Dict[str, float]          # at the window's close
    trace_path: Optional[str] = None
    trace_span: Optional[List[float]] = None   # [t0, t1] on the window clock
    compiles_in_window: int = 0
    drain_s: float = 0.0
    #: seconds the profiler's stop held the loop after the close: the call
    #: itself and the first iteration after it, which the device makes
    #: wait; the wait for what is in flight starts when that has ended
    trace_stall_s: float = 0.0


def run_window(adapter: Any, schedule: List[Any], seconds: float,
               trace_dir: Optional[str] = None,
               clock: Any = None) -> WindowResult:
    """Offer ``schedule`` for ``seconds`` on one thread: before each
    iteration submit every request that is due, after it stamp every
    token that has appeared. Then stop submitting and wait, up to
    ``DRAIN_SECONDS``, for what is in flight."""
    import jax

    note = jax.profiler.TraceAnnotation
    reqs = [RequestRecord(r.index, r.due_s, r.prompt, r.max_new)
            for r in schedule]
    iters: List[IterationRecord] = []
    live: List[RequestRecord] = []
    nxt = 0
    tracing = False
    trace_span: Optional[List[float]] = None
    trace_from = max(0.0, seconds - TRACE_SECONDS) if trace_dir else math.inf
    c0 = c1 = clock.snapshot()["compiles"] if clock else 0
    stats_start = adapter.stats()
    stats_end = None
    steps_seen = stats_start["decode_steps"]
    t_start = time.perf_counter()
    stall = 0.0       # seconds the profiler's stop held the loop
    stop_at = None    # when the profiler's stop began, while it holds
    while True:
        now = time.perf_counter() - t_start
        closed = now >= seconds
        # every request is due inside the window; one whose due time fell
        # into the window's last iteration is offered when that ends
        with note("bench.submit"):
            while nxt < len(reqs) and reqs[nxt].due_s <= now:
                r = reqs[nxt]
                r.handle = adapter.submit(r.prompt, r.max_new)
                r.submit_s = time.perf_counter() - t_start
                live.append(r)
                nxt += 1
        if closed and stats_end is None:
            stats_end = adapter.stats()
            c1 = clock.snapshot()["compiles"] if clock else 0
        if tracing and closed:
            stop_at = now
            with note("bench.stop_trace"):
                jax.profiler.stop_trace()
            tracing = False
            trace_span[1] = now
            stall = (time.perf_counter() - t_start) - stop_at
            continue
        if closed and (not live or (
                stop_at is None
                and now >= seconds + DRAIN_SECONDS + stall)):
            break
        if not closed and not tracing and trace_span is None \
                and now >= trace_from:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing = True
            trace_span = [time.perf_counter() - t_start, math.nan]
            continue
        if not live:
            until = min(reqs[nxt].due_s if nxt < len(reqs) else seconds,
                        seconds)
            with note("bench.wait_arrival"):
                time.sleep(max(0.0, until - (time.perf_counter() - t_start)))
            continue
        t0 = time.perf_counter() - t_start
        with note("bench.step_iteration"):
            adapter.step()
        t1 = time.perf_counter() - t_start
        if stop_at is not None:
            # the first iteration after the profiler stopped: the device
            # holds it back, so it belongs to the stall and not to the wait
            stall = t1 - stop_at
            stop_at = None
        with note("bench.stamp"):
            st = adapter.stats()
            rec = IterationRecord(
                t0, t1, in_window=t1 <= seconds, traced=tracing,
                pending_after=adapter.pending(),
                decode_steps=int(st["decode_steps"] - steps_seen))
            steps_seen = st["decode_steps"]
            for r in live:
                before = len(r.token_s)
                after = adapter.progress(r.handle)
                if after > before:
                    r.token_s.extend([t1] * (after - before))
                    rec.progress.append((r.index, before, after))
            live = [r for r in live if not r.finished]
            iters.append(rec)
    drain = (time.perf_counter() - t_start) - seconds - stall
    return WindowResult(
        seconds=seconds, requests=reqs, iterations=iters,
        stats_start=stats_start, stats_end=stats_end or adapter.stats(),
        trace_path=trace_dir if trace_span else None,
        trace_span=trace_span, compiles_in_window=c1 - c0,
        drain_s=max(0.0, drain), trace_stall_s=stall)


# --------------------------------------------------------------------------- #
# metrics
# --------------------------------------------------------------------------- #

@dataclass
class Context:
    """What a metric's reader may look at."""
    cell: Cell
    window: WindowResult
    setup_s: float
    peaks: Dict[str, float]
    adapter: Any                    # for bucket_of(); freed before the check
    trace: Any = None               # trace_reduce.Reduced, traced runs only


def read_metrics(kind: str, rows: List[Dict[str, Any]], ctx: Context
                 ) -> Dict[str, Dict[str, Any]]:
    """``{name: {"value", "unit"}}`` for every metric whose reader finds
    something to read; a reader that returns None is left out."""
    out: Dict[str, Dict[str, Any]] = {}
    for row in rows:
        value = reader_of(kind, row["name"]).read(ctx)
        if value is None:
            continue
        value = float(value)
        if not math.isfinite(value):
            continue
        out[row["name"]] = {"value": value, "unit": row["unit"]}
    return out


# --------------------------------------------------------------------------- #
# correct
# --------------------------------------------------------------------------- #

def check_sample(window: WindowResult, n: int, seed: int
                 ) -> List[RequestRecord]:
    """``n`` of the requests the window finished, drawn from the seed,
    the longest (prompt plus output) always among them."""
    done = [r for r in window.requests if r.finished]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt.size + r.max_new, -r.index))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    picks = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[int(i)] for i in sorted(picks)]


def resident_state(cell: Cell, window: WindowResult, adapter: Any
                   ) -> Dict[int, Any]:
    """``{request index: (keys, values)}`` for the slots whose rows the
    check compares (``check.kv_slots`` of them), copied to the host."""
    by_handle = {id(r.handle): r.index for r in window.requests
                 if r.handle is not None}
    most = int(cell.params["check"].get("kv_slots", 0))
    return {by_handle[id(req)]: (k, v)
            for req, k, v in adapter.resident(most)
            if id(req) in by_handle}


def compared_requests(cell: Cell, window: WindowResult,
                      state: Dict[int, Any], seed: int
                      ) -> List[RequestRecord]:
    """The requests a run compares: the seeded sample and the requests
    whose rows were read out of the store."""
    sample = check_sample(window, int(cell.params["check"]["requests"]),
                          seed)
    return sample + [r for r in window.requests
                     if r.index in state and r not in sample]


def decide_correct(cell: Cell, window: WindowResult, served: Dict[int, Any],
                   state: Dict[int, Any], seed: int,
                   claimed: Optional[Dict[int, Any]] = None
                   ) -> Dict[str, Dict[str, float]]:
    """The numbers compared, each beside its limit. ``served`` maps a
    request's index to the tokens the timed path handed out for it,
    ``state`` to the keys and values its programs left in the store.
    ``claimed``, for a control that does not decode, maps an index to the
    token it puts first at each served position: the gap read is then
    that token's, along the served sequence."""
    ref_mod = importlib.import_module(
        f"{__package__}.reference.{cell.config['reference']}")
    check = cell.params["check"]
    sample = compared_requests(cell, window, state, seed)
    short = sum(1 for r in window.requests
                if len(served.get(r.index, ())) != r.max_new)
    ref = ref_mod.Reference(cell.config, seed)
    try:
        widest = kv_widest = 0.0
        for r in sample:
            got = ref.score(r.prompt, served[r.index],
                            query=claimed[r.index] if claimed else None,
                            kv=state.get(r.index))
            widest = max(widest, float(np.max(got[0])))
            if r.index in state:
                kv_widest = max(kv_widest, got[2])
    finally:
        ref.free()
    limits = check["limits"]
    want = min(int(check["requests"]), len(window.requests))
    kv_want = min(int(check.get("kv_slots", 0)), len(window.requests))
    return {
        "gap_max": {"value": widest, "limit": float(limits["gap_max"])},
        "kv_gap_max": {"value": kv_widest,
                       "limit": float(limits["kv_gap_max"])},
        "short_outputs": {"value": float(short), "limit": 0.0},
        "sample_shortfall": {"value": float(max(0, want - len(sample))),
                             "limit": 0.0},
        "kv_shortfall": {"value": float(max(0, kv_want - len(state))),
                         "limit": 0.0},
    }


def longest_iterations(window: WindowResult) -> Dict[str, Any]:
    """The loop's count of iterations and its longest one, [start, length]
    in seconds, inside the window and after its close: a run that reads
    far off shows here whether one iteration stood still."""
    out: Dict[str, Any] = {}
    for name, late in (("in_window", False), ("after_close", True)):
        its = [it for it in window.iterations
               if (it.t0 >= window.seconds) == late]
        worst = max(its, key=lambda it: it.t1 - it.t0, default=None)
        out[name] = {"iterations": len(its),
                     "longest": [worst.t0, worst.t1 - worst.t0]
                     if worst else None}
    return out


def live_kv_rows(window: WindowResult) -> Optional[float]:
    """Rows of the KV store that hold a running request's prompt and
    tokens, averaged over the window's time: the store's live part, as
    against its allocated size."""
    size = {r.index: int(r.prompt.size) for r in window.requests}
    rows = span = 0.0
    for it in window.iterations:
        if it.in_window:
            rows += (it.t1 - it.t0) * sum(size[i] + after
                                          for i, _, after in it.progress)
            span += it.t1 - it.t0
    return rows / window.seconds if span else None


def unfinished(window: WindowResult, most: int = 8) -> List[List[Any]]:
    """[index, due_s, submit_s, wanted, got] of requests that did not
    finish: what the next reader needs to see why ``failed`` is not 0."""
    return [[r.index, r.due_s, r.submit_s, r.max_new, len(r.token_s)]
            for r in window.requests if not r.finished][:most]


def is_correct(compared: Dict[str, Dict[str, float]]) -> bool:
    """No number compared may pass its limit (a NaN passes every one)."""
    return all(row["value"] <= row["limit"] for row in compared.values())


# --------------------------------------------------------------------------- #
# one run
# --------------------------------------------------------------------------- #

def set_up(cell: Cell, seed: int, seconds: float,
           marks: Optional[Dict[str, float]] = None):
    """The served engine with its weights from the seed, the window's
    schedule, and one warm call of every program that schedule uses.
    ``marks`` is given the time at which the engine stood."""
    from .traffic import open_loop

    builder = importlib.import_module(
        f"{__package__}.builders.{cell.config['builder']}")
    adapter = builder.build(cell.config, seed)
    if marks is not None:
        marks["built"] = time.time()
    vocab = int(cell.config["vocab_size"])
    sched = open_loop.schedule(cell.mix, float(cell.params["rate_rps"]),
                               seconds, seed, vocab)
    adapter.warm([r.prompt.size for r in sched], vocab)
    return adapter, sched


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float, need_tpu: bool = True) -> Dict[str, Any]:
    """Set up, warm, measure, check; returns the result line's object."""
    import jax

    from . import trace_reduce
    from .clock import CompileClock
    from .peaks import peaks_for

    device = require_chips(cell.chips) if need_tpu \
        else device_info(cell.chips)
    place_compile_cache()
    clock = CompileClock()
    marks = {"device": time.time()}
    adapter, sched = set_up(cell, seed, seconds, marks)
    trace_dir = None
    if trace:
        trace_dir = os.path.join(CHECKOUT, ".bench_trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    setup = clock.snapshot()
    setup_s = time.time() - t_process
    window = run_window(adapter, sched, seconds, trace_dir, clock)
    device["memory_peak_bytes"] = memory_peak_bytes(cell.chips)
    served = {r.index: adapter.tokens(r.handle) for r in window.requests
              if r.handle is not None}
    state = resident_state(cell, window, adapter)
    peaks = peaks_for(device["kind"]) if need_tpu else {}
    ctx = Context(cell, window, setup_s, peaks, adapter)
    breakdown = None
    if trace and window.trace_path:
        ctx.trace = trace_reduce.reduce_dir(window.trace_path)
        shutil.rmtree(os.path.join(CHECKOUT, ".bench_trace"),
                      ignore_errors=True)
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        breakdown = ctx.trace.breakdown()
    metrics = read_metrics("layers" if trace else "end_to_end",
                           cell.per_layer if trace else cell.end_to_end, ctx)
    # the mean beside the tail, in every run, for whoever reads the spread
    ttft_mean = importlib.import_module(
        f"{__package__}.layers.ttft_mean_ms").read(ctx)
    ctx.adapter = None
    for r in window.requests:
        r.handle = None
    adapter.free()
    del adapter
    compared = decide_correct(cell, window, served, state, seed)
    attempted = len(window.requests)
    failed = sum(1 for r in window.requests if not r.finished)
    out: Dict[str, Any] = {
        "correct": is_correct(compared) and failed == 0,
        "attempted": attempted, "failed": failed,
        "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["harness"] = {
        "workload": cell.name, "seed": seed, "seconds": seconds,
        "trace": int(trace), "setup_compile_s": setup["compile_s"],
        # where set-up's seconds went: to the first answer of the device,
        # the engine with its weights, the warm calls
        "setup_parts_s": {"device": marks["device"] - t_process,
                          "build": marks["built"] - marks["device"],
                          "warm": t_process + setup_s - marks["built"]},
        "cache_hits": setup["cache_hits"],
        "cache_misses": setup["cache_misses"],
        "compiles_in_window": window.compiles_in_window,
        "drain_s": window.drain_s,
        "trace_stall_s": window.trace_stall_s,
        "iterations": longest_iterations(window),
        "live_kv_rows": live_kv_rows(window),
        "ttft_mean_ms": ttft_mean,
        "unfinished": unfinished(window),
        "jax": jax.__version__}
    out["compared"] = compared
    return out


def print_result(out: Dict[str, Any]) -> None:
    """The numbers compared as the last lines of standard error, the
    result as the last line of standard output."""
    sys.stdout.flush()
    for name, row in out["compared"].items():
        print(f"compared {name}: value {row['value']!r} limit "
              f"{row['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
