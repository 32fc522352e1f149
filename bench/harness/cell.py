"""One run of one cell: set-up, the measured window, metrics, the check.

Order of a run:

1. Find the chip (no fallback: a run without the chips the cell asks for
   ends before it prints a result), turn on the compile cache inside the
   checkout, build the configuration's stack with weights from the seed.
2. Warm every program the window uses: the fuser's prefill and join rungs
   and its decode step, then whole batches served through the scheduler.
   ``setup_s`` runs from process start to the first request being due.
3. The window: the traffic mix drives the scheduler for ``--seconds``.
   Every streamed token is timestamped as it reaches the client's future.
   With ``--trace 1`` the profiler records a few seconds in the middle and
   the per-layer metrics are read; otherwise the end-to-end ones.  Each
   metric is read by its own file, ``bench/metrics/<name>.py``.
4. Drain, read the peak device memory, free the program's state, and
   compare a seeded sample of what was served with the plain references,
   by the configuration module's own ``check``.
"""

from __future__ import annotations

import contextlib
import gc
import os
import pathlib
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from harness import ensemble, spec, traffic, window
from harness.peaks import peaks_for

TRACE_LEAD_S = 2.0  # into the window before the profiler starts
TRACE_S = 4.0  # traced stretch
SAMPLE = 48  # served requests the reference reads, the longest among them
COMPILE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def process_start_time() -> float:
    """Wall-clock time this process started (Linux), else now."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


def find_devices(chips: int, require_chip: bool):
    import jax

    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    peaks = None
    if require_chip:
        try:
            peaks = peaks_for(platform, kind)
        except RuntimeError as exc:
            raise NoChip(str(exc)) from None
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips], {"platform": platform, "kind": kind, "count": len(devs)}, peaks


def enable_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent cache at a fixed directory inside the checkout,
    handed to the program through the variable its own set-up reads; every
    compile is persisted, whatever it cost, so a fast and a slow host keep
    the same programs."""
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    from repro.launch.compile_cache import enable_compile_cache as program_cache

    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class Spans:
    """Host spans around the program's methods, wrapped on the instance."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.records: List[tuple] = []

    def wrap(self, obj, attr: str, name: str) -> None:
        import jax

        orig = getattr(obj, attr)
        records, annotate = self.records, self.annotate

        def wrapped(*args, **kwargs):
            t0 = time.perf_counter()
            ctx = jax.profiler.TraceAnnotation(name) if annotate else contextlib.nullcontext()
            with ctx:
                out = orig(*args, **kwargs)
            records.append((name, t0, time.perf_counter()))
            return out

        setattr(obj, attr, wrapped)


class Stream:
    """Timestamps every token as the scheduler hands it to its future."""

    def __init__(self, sched):
        self.tokens: List[tuple] = []  # (time, seq)
        self.last: Dict[int, List[int]] = {}
        orig = sched._stream_push

        def stream_push(batch, t0):
            on_token = orig(batch, t0)

            def record(i, toks):
                on_token(i, toks)
                seq = batch[i].future.seq
                self.tokens.append((time.perf_counter(), seq))
                self.last[seq] = toks
            return record

        sched._stream_push = stream_push


class Compiles:
    """Compile requests JAX reports (each persistent-cache hit or miss
    is one), and which of them the cache answered."""

    def __init__(self):
        self.events: List[tuple] = []

    def on_event(self, event, **kwargs):
        if event in (COMPILE_REQUEST, CACHE_HIT):
            self.events.append((time.perf_counter(), event))

    def count(self, t0: float, t1: float, event: str = None) -> int:
        event = event or COMPILE_REQUEST
        return sum(1 for t, e in self.events if t0 <= t <= t1 and e == event)


def _host_usage() -> Dict[str, float]:
    """This process's CPU seconds so far, and the machine's CPU seconds
    taken by its hypervisor (steal): set against the window's length they
    show whether a slow run lacked CPU or did more work."""
    t = os.times()
    usage = {"user_s": t.user, "system_s": t.system}
    try:
        with open("/proc/stat") as f:
            fields = f.readline().split()
        usage["steal_s"] = int(fields[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        pass
    return usage


def _stalls_ms(per_request: Dict[int, List[float]], t0: float, t1: float,
               over_s: float = 0.05) -> List[float]:
    """The ten longest waits, in ms, between two tokens of one request
    inside the window, each wait counted once however many requests
    shared it (a slow decode step stalls every slot)."""
    waits = {}
    for times in per_request.values():
        inside = [t for t in times if t0 <= t <= t1]
        for a, b in zip(inside, inside[1:]):
            if b - a > over_s:
                waits[round(b, 3)] = 1e3 * (b - a)
    return sorted(waits.values(), reverse=True)[:10]


class GcTimer:
    """Times the interpreter's garbage collections until ``stop``."""

    def __init__(self):
        self.ms: List[tuple] = []  # (generation, ms)
        self._t = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.ms.append((info["generation"], 1e3 * (time.perf_counter() - self._t)))

    def stop(self) -> None:
        gc.callbacks.remove(self._on_gc)

    def summary(self) -> Dict[str, float]:
        return {"collections": len(self.ms),
                "full_collections": sum(1 for g, _ in self.ms if g == 2),
                "total_ms": sum(ms for _, ms in self.ms),
                "max_ms": max((ms for _, ms in self.ms), default=0.0)}


class Context:
    """What a metric reader (``bench/metrics/<name>.py``) may read:
    ``window`` (t0, t1) and ``setup_s`` on the host clock, the host
    ``spans`` and ``compiles``, the reduced ``trace`` (None without the
    profiler), streamed ``tokens`` and their times ``per_request``, the
    ``served`` requests, the configuration, mix, ``peaks`` and ``chips``,
    the dispatched ``batches`` and when each request was ``due``."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def spans_in(self, name: str) -> List[float]:
        t0, t1 = self.window
        return [(e - s) for n, s, e in self.spans if n == name and t0 <= s and e <= t1]

    def span_ms(self, name: str) -> Optional[float]:
        d = self.spans_in(name)
        return 1e3 * sum(d) / len(d) if d else None


def run(workload: str, seed: int, seconds: float, trace: bool, t_start: float,
        root: pathlib.Path = spec.ROOT, require_chip: bool = True,
        cfg_override: Optional[dict] = None, fault=None, control: bool = False) -> dict:
    import jax

    bench = spec.load_benchmark(root)
    wl = spec.workload(bench, workload)
    devs, device, peaks = find_devices(wl["chips"], require_chip)
    enable_compile_cache(root)
    cfg = spec.load_config(bench, wl["config"], root)
    if cfg_override:
        cfg = {**cfg, **cfg_override}
    mix = traffic.validate(spec.load_traffic(wl["traffic"], root))
    mod = spec.config_module(wl["config"], root)

    stack = mod.build(cfg, seed)
    if fault is not None:
        fault(stack)
    stack.warm_programs([mix["batch"]])
    sched = stack.scheduler()
    stream = Stream(sched)
    spans = Spans(annotate=trace)
    stack_spans = sorted(stack.spans)
    for name, (obj, attr) in stack.spans.items():
        spans.wrap(obj, attr, name)

    def make_request(q):
        return stack.make_request(q, mix["epsilon"], mix["max_new_tokens"])

    # warm-up: whole batches of the window's own shape, from their own stream
    warm_q = traffic.Queries(seed, 0)
    warm = [sched.submit(make_request(q))
            for q in warm_q.take(mix["batch"] * mix["warmup_batches"])]
    sched.flush()
    sched.join()
    for f in warm:
        f.result(timeout=600)
    n_warm_events = len(sched.events)

    # set-up's objects leave the collector's view, so no long collection
    # of them lands inside the window
    gc.collect()
    gc.freeze()
    compiles = Compiles()
    jax.monitoring.register_event_listener(compiles.on_event)
    driver = traffic.Driver(mix, seed, sched.submit, make_request)
    setup_s = time.time() - t_start

    trace_dir = root / ".bench_trace"
    profiler = _Profiler(trace_dir) if trace else None
    if profiler:
        profiler.arm(TRACE_LEAD_S, TRACE_S)
    host_before = _host_usage()
    gc_timer = GcTimer()
    t0, t1 = driver.run(seconds, tick=sched.tick)
    gc_timer.stop()
    host_after = _host_usage()
    if profiler:
        profiler.finish()
    sched.flush()
    sched.join()
    t_drained = time.perf_counter()
    gc.unfreeze()  # the program's state has to be collectable once freed

    futures = driver.futures
    failed = 0
    served: Dict[int, ensemble.Served] = {}
    for seq, f in futures.items():
        try:
            resp = f.result(timeout=120)
        except Exception:  # a request that failed counts against the run
            failed += 1
            continue
        toks = stream.last.get(seq, [])
        served[seq] = ensemble.Served(
            query=driver.records[seq], eps=mix["epsilon"], tokens=list(toks),
            mask=np.asarray(resp.mask, bool), scores=np.asarray(resp.predicted_quality),
            member_texts=list(resp.member_texts), cap=mix["max_new_tokens"])
    batches = [e["reqs"] for e in sched.events[n_warm_events:] if e["event"] == "dispatch"]
    sched.close()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    device["memory_peak_bytes"] = int(peak)

    times, per_req = window.split_window(stream.tokens, t0, t1)
    reduced = profiler.reduce() if trace else None
    ctx = Context(window=(t0, t1), setup_s=setup_s, spans=list(spans.records),
                  compiles=compiles, trace=reduced, tokens=times, per_request=per_req,
                  served=served, cfg=cfg, mix=mix, peaks=peaks, chips=wl["chips"],
                  batches=batches, due=driver.due)
    if trace:
        device["busy_s"] = reduced.busy_s(0)
        device["window_s"] = reduced.window_s
    # with the profiler off the cell's end-to-end metrics, with it on its
    # per-layer ones; each is read by its own file, found by its name
    wanted = (spec.per_layer_for(bench, workload) if trace
              else spec.end_to_end_for(bench, workload))
    result_metrics = {}
    for m in wanted:
        value = spec.metric_reader(m["name"], root)(ctx)
        if value is None and not trace:
            raise RuntimeError(f"end-to-end metric {m['name']} found nothing to read")
        if value is not None:
            result_metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    jax.monitoring.unregister_event_listener(compiles.on_event)

    # the check runs once the program's state is gone: futures and the
    # generator hold the scheduler, which holds the server and its caches
    attempted = len(futures)
    driver.futures.clear()
    driver.submit = None
    del sched, stream, spans, warm, futures, f
    stack.free_program()
    if not served:
        raise RuntimeError(f"no request of {attempted} was served")
    rng = np.random.default_rng([seed, 2])
    done = sorted(served)
    longest = max(done, key=lambda r: len(served[r].tokens))
    others = [r for r in done if r != longest]
    pick = rng.choice(len(others), size=min(SAMPLE - 1, len(others)), replace=False)
    sample = [longest] + [others[i] for i in sorted(pick)]
    checks = mod.check(stack, served, batches, sample)
    controls = mod.check(stack, served, batches, sample, control=True) if control else None
    out = {
        "correct": failed == 0 and all(c.ok for c in checks),
        "attempted": attempted,
        "failed": failed,
        "metrics": result_metrics,
        "device": device,
    }
    out["window"] = {"seconds": t1 - t0, "drain_s": t_drained - t1,
                     "generator_late_p95_ms": 1e3 * window.percentile(driver.late_s, 95),
                     "served": len(served), "batches": len(batches),
                     "compile_requests": compiles.count(t0, t1),
                     "compile_cache_hits": compiles.count(t0, t1, CACHE_HIT),
                     "host_ms_per_call": {name: ctx.span_ms(name) for name in stack_spans},
                     "host_ms_max": {name: 1e3 * max(ctx.spans_in(name), default=0.0)
                                     for name in stack_spans},
                     "stalls_ms": _stalls_ms(per_req, t0, t1),
                     "gc": gc_timer.summary(),
                     "host": {k: host_after[k] - host_before.get(k, 0.0) for k in host_after}}
    if trace:
        out["breakdown"] = reduced.breakdown
        out["window"]["device_clock_offset_ns"] = reduced.clock_offset_ns
    if controls:
        # the control judged by the same comparison and limits as the program
        out["control_correct"] = all(c.ok for c in controls)
        out["control"] = {c.name: c.value for c in controls}
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    return out


class _Profiler:
    """Starts the profiler ``lead`` seconds into the window, from a timer
    thread, and stops it ``length`` seconds later; the stretch is wrapped
    in a ``bench.window`` annotation."""

    def __init__(self, trace_dir: pathlib.Path):
        self.dir = trace_dir
        shutil.rmtree(trace_dir, ignore_errors=True)
        self._thread = None
        self.error = None

    def arm(self, lead: float, length: float) -> None:
        import threading

        def body():
            import jax

            try:
                time.sleep(lead)
                jax.profiler.start_trace(str(self.dir))
                with jax.profiler.TraceAnnotation("bench.window"):
                    time.sleep(length)
                jax.profiler.stop_trace()
            except Exception as exc:  # reported after the window
                self.error = exc

        self._thread = threading.Thread(target=body, daemon=True)
        self._thread.start()

    def finish(self) -> None:
        self._thread.join()
        if self.error is not None:
            raise self.error

    def reduce(self):
        from harness import trace as trace_mod

        reduced = trace_mod.reduce(trace_mod.load(str(self.dir)))
        shutil.rmtree(self.dir, ignore_errors=True)
        return reduced


def print_result(out: dict) -> None:
    """The checks as the last lines on stderr, the result as the last line
    on stdout, with the checks as its last key."""
    import json

    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
