"""One run of one benchmark cell: set-up, the measured window, the check.

Set-up makes the weights on the device from the seed, builds the
program's ``ServerModel`` on the Pallas lane and warms exactly the
(length bucket, beta, capture, B) executables the cell's traffic can
reach.  The window then drives the serving grid from a real-clock
replica loop: a FIFO queue of due offloads, waves formed by the
program's ``serve.scheduler.form_wave`` and capped at the largest batch
bucket, each wave staged (``stage_frames``) and dispatched
(``infer_wave(..., defer=True)``) before the previous one is waited on
(``PendingWave.wait``).  Each call into the program runs inside a
``chipbench.*`` profiler span.  After the window the program is freed
and the plain reference (``reference.py``) checks a seeded sample of
what the timed waves produced.
"""
from __future__ import annotations

import gc
import heapq
import importlib.util
import json
import os
import sys
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
CACHE_DIR = BENCH_DIR / ".jax_cache"
TRACE_DIR = BENCH_DIR / ".trace"
TRACE_SECONDS = 8.0


class RunError(Exception):
    """A run that cannot produce a result (no chip, a compile in the
    window, a missing program)."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# set-up


def configure_jax(cache_dir: Path = CACHE_DIR) -> None:
    """Kernel defaults instead of a noisy sweep, and the persistent
    compile cache at a fixed path inside the checkout (on an
    accelerator; a CPU rehearsal keeps none)."""
    os.environ["REPRO_AUTOTUNE"] = "0"
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise RunError(f"the program is not here: no {src / 'repro'}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import jax
    if jax.devices()[0].platform == "cpu":
        # rehearsals and tests: nothing worth keeping, and XLA:CPU warns
        # on reading back its own entries
        jax.config.update("jax_enable_compilation_cache", False)
        return
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no size limit: a limit below one cell's executables (ViTDet-L's
    # grid serialises to some hundreds of MB) makes the LRU evict each
    # entry before the next run asks for it, so every lookup misses
    jax.config.update("jax_compilation_cache_max_size", -1)


class CompileCounter:
    """Counts JAX compile requests and persistent-cache hits/misses."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        self._event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(event, duration, **kw):
            if event == self._event:
                self.compiles += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def load_json(path: Path) -> Dict:
    return json.loads(Path(path).read_text())


def find_cell(bench: Dict, workload: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise RunError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return cell, cfg_entry


def program_config(model: Dict):
    """The program's ModelConfig for the sizes of a configuration file."""
    from repro.configs.vitdet_l import CONFIG
    from repro.models.config import MixedResConfig, ViTConfig
    img = model["img_size"]
    return CONFIG.replace(
        name=model["name"], n_layers=model["n_layers"],
        d_model=model["d_model"], n_heads=model["n_heads"],
        n_kv_heads=model["n_kv_heads"], head_dim=model["head_dim"],
        d_ff=model["d_ff"], norm_eps=model["norm_eps"],
        max_seq_len=(img // model["patch_size"]) ** 2,
        vit=ViTConfig(img_size=(img, img), patch_size=model["patch_size"],
                      window_size=model["window_size"],
                      n_subsets=model["n_subsets"],
                      out_channels=model["out_channels"],
                      n_classes=model["n_classes"]),
        mixed_res=MixedResConfig(enabled=True, window=model["window_size"],
                                 downsample=model["downsample"],
                                 n_subsets=model["n_subsets"]))


def seed32(seed: int) -> int:
    return int(np.random.default_rng([seed, 0x5EED]).integers(2 ** 31))


def plan_space(traffic: Dict, n_regions: int, beta: int):
    """The (n_low, n_reuse, beta, capture) plan shapes the traffic can
    emit: every histogram entry with every smaller REUSE count (a
    session may find fewer eligible regions), plus the full-res frame."""
    cap = beta if traffic["stateful"] else 0
    space = set()
    if traffic.get("bootstrap_full") or any(
            nl == 0 and nr == 0 for hist in traffic["plans"].values()
            for nl, nr, _ in hist):
        space.add((0, 0, 0, cap))
    for hist in traffic["plans"].values():
        for nl, nr, _ in hist:
            for r in range(nr + 1 if traffic["stateful"] else 1):
                r = min(r, n_regions - 1)
                if nl == 0 and r == 0:
                    space.add((0, 0, 0, cap))
                else:
                    space.add((nl, r, beta, beta))
    return sorted(space)


# ---------------------------------------------------------------------------
# the replica loop


@dataclass
class Job:
    off: object                   # traffic.Offload
    due: float
    lb: int
    dispatch: float = 0.0
    done: float = 0.0
    wave: int = -1
    row: int = -1


@dataclass
class Wave:
    wid: int
    jobs: List[Job]
    lb: int
    host_s: float
    dispatch: float
    pending: object = None
    done: float = 0.0


@dataclass
class LoopResult:
    jobs: List[Job] = field(default_factory=list)
    waves: List[Wave] = field(default_factory=list)
    caches: Dict = field(default_factory=dict)
    spans: List = field(default_factory=list)   # (name, t0, t1, stats)
    gc_pauses: List = field(default_factory=list)   # (t0, seconds)


class GcPauses:
    """Start and length of each full (generation 2) collection of
    Python's cyclic garbage collector while installed."""

    def __init__(self):
        self.pauses: List = []
        self._t = None

    def __call__(self, phase, info):
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((self._t, time.perf_counter() - self._t))
            self._t = None


@contextmanager
def span(log: List, name: str, clock=time.perf_counter, **stats):
    """A ``chipbench.<name>`` profiler span that is also logged on the
    host clock, where the trace keeps no host events."""
    import jax
    t = clock()
    with jax.profiler.TraceAnnotation(f"chipbench.{name}", **stats):
        yield
    log.append((f"chipbench.{name}", t, clock(), stats))


def serve(sm, sessions, frames, traffic: Dict, beta: int, start: float,
          stop: float, tick: Optional[Callable[[float], None]] = None,
          clock: Callable[[], float] = time.perf_counter) -> LoopResult:
    """Serve the sessions from ``start`` until ``stop`` (host clock),
    then drain the wave in flight.  ``tick(now)`` runs at the
    top of every turn of the loop."""
    from repro.core.partition import RegionPlan
    from repro.serve.request import FeatureCache
    from repro.serve.scheduler import form_wave

    cap = max(traffic["batch_buckets"])
    stateful = traffic["stateful"]
    caches = {s.sid: (FeatureCache(sm.part.n_regions,
                                   max_age=traffic["reuse_k"])
                      if stateful else None) for s in sessions}
    by_id = {s.sid: s for s in sessions}
    res = LoopResult()
    upcoming = []                                # (due, sid) heap
    for s in sessions:
        off = s.next_offload()
        heapq.heappush(upcoming, (start + s.due_after(off, 0.0), s.sid, off))
    queue: List[Job] = []
    inflight: Optional[Wave] = None

    def lb_of(off) -> int:
        return sm.plan_length_bucket(RegionPlan(off.states))

    while True:
        now = clock()
        if tick is not None:
            tick(now)
            now = clock()
        while upcoming and upcoming[0][0] <= now:
            due, sid, off = heapq.heappop(upcoming)
            queue.append(Job(off, due, lb_of(off)))
        launched = None
        if queue and now < stop:
            with span(res.spans, "form_wave", clock):
                wave_jobs, queue, lb = form_wave(
                    queue, key_fn=lambda j: j.lb, cap=cap)
            wid = len(res.waves)
            plans = [RegionPlan(j.off.states) for j in wave_jobs]
            with span(res.spans, "batch", clock):
                batch = frames[[j.off.frame for j in wave_jobs]]
            t0 = clock()
            with span(res.spans, "stage", clock, wave=wid):
                staged = sm.stage_frames(batch)
            with span(res.spans, "dispatch", clock, wave=wid):
                pending = sm.infer_wave(
                    staged, plans, beta,
                    caches=([caches[j.off.session] for j in wave_jobs]
                            if stateful else None),
                    frame_ids=[j.off.index for j in wave_jobs],
                    capture_beta=beta if stateful else 0, defer=True)
            t1 = clock()
            for i, j in enumerate(wave_jobs):
                j.dispatch, j.wave, j.row = t0, wid, i
            launched = Wave(wid, wave_jobs, lb, t1 - t0, t0, pending)
            res.waves.append(launched)
            res.jobs.extend(wave_jobs)
        if inflight is not None:
            with span(res.spans, "wait", clock, wave=inflight.wid):
                inflight.pending.wait()
            done = clock()
            inflight.done = done
            for j in inflight.jobs:
                j.done = done
                s = by_id[j.off.session]
                nxt = s.next_offload()
                due = start + s.due_after(nxt, done - start)
                if due < stop:
                    heapq.heappush(upcoming, (due, s.sid, nxt))
                else:
                    s.offloads.pop()         # never offered
            inflight = None
        if launched is not None:
            inflight = launched
            continue
        # nothing in flight and nothing due: stop, or sleep to the next due
        if clock() >= stop or not upcoming:
            break
        wait = upcoming[0][0] - clock()
        if wait > 0:
            with span(res.spans, "idle", clock):
                time.sleep(wait)
    res.caches = caches
    return res


# ---------------------------------------------------------------------------
# metrics


def percentile(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


def end_to_end(res: LoopResult, t0: float, t1: float) -> Dict:
    done = [j for j in res.jobs if t0 <= j.done <= t1 and j.done > 0]
    lat = [(j.done - j.due) * 1e3 for j in done]
    if not lat:
        raise RunError("no offload completed in the window")
    return {"offload_p90_ms": percentile(lat, 90),
            "offload_p50_ms": percentile(lat, 50),
            "frames_per_s": len(done) / (t1 - t0),
            "_completed": len(done)}


def timeline(res: LoopResult, t0: float, t1: float) -> str:
    """The window's offloads completed in each second, its waves by size
    and its longest wave: where a run falls behind, and how."""
    done = [j.done - t0 for j in res.jobs if t0 <= j.done <= t1]
    per_s = np.histogram(done, bins=max(int(round(t1 - t0)), 1),
                         range=(0.0, t1 - t0))[0]
    waves = [w for w in res.waves if t0 <= w.dispatch <= t1 and w.done]
    sizes: Dict[int, int] = {}
    for w in waves:
        sizes[len(w.jobs)] = sizes.get(len(w.jobs), 0) + 1
    longest = max(((w.done - w.dispatch) * 1e3 for w in waves), default=0.0)
    pauses = [d * 1e3 for t, d in res.gc_pauses if t0 <= t <= t1]
    return (f"offloads done per second {' '.join(map(str, per_s))}; waves "
            f"by size {dict(sorted(sizes.items()))}; longest wave "
            f"{longest:.1f} ms; full collections {len(pauses)}, longest "
            f"{max(pauses, default=0.0):.1f} ms")


def load_reader(name: str):
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# warm pass: every shape the window will use, served once in set-up


def _lb_plans(sm, states_fn, beta: int):
    """One reuse plan and one LOW-only plan per mixed length bucket."""
    from repro.core.partition import RegionPlan
    nR = sm.part.n_regions
    reps: Dict[int, Dict[str, np.ndarray]] = {}
    for n_low in range(nR + 1):
        for n_reuse in range(nR - n_low):
            st = states_fn(n_low, n_reuse)
            lb = sm.plan_length_bucket(RegionPlan(st))
            if lb == 0:
                continue
            kind = "reuse" if n_reuse else "low"
            reps.setdefault(lb, {}).setdefault(kind, st)
    return reps


def warm_pass(sm, frames, traffic: Dict, beta: int, lbs) -> int:
    """Serve throwaway waves through every (length bucket, B bucket) the
    traffic reaches, with and without REUSE rows, so that every small op
    on the serving path (tile stacks, decode slices) is compiled before
    the window.  Returns the number of waves served."""
    from repro.core.partition import FULL, LOW, REUSE, RegionPlan
    from repro.serve.request import FeatureCache
    nR = sm.part.n_regions

    def states(n_low, n_reuse):
        st = np.full((nR,), FULL, np.int8)
        st[:n_reuse] = REUSE
        st[n_reuse:n_reuse + n_low] = LOW
        return st

    reps = _lb_plans(sm, states, beta)
    stateful = traffic["stateful"]
    full = RegionPlan(states(0, 0))
    n = 0
    for B in sorted(traffic["batch_buckets"]):
        idx = np.arange(B) % len(frames)
        caches = ([FeatureCache(nR, max_age=traffic["reuse_k"])
                   for _ in range(B)] if stateful else None)
        sm.infer_wave(sm.stage_frames(frames[idx]), [full] * B, beta,
                      caches=caches, frame_ids=list(range(B)),
                      capture_beta=beta if stateful else 0,
                      defer=True).wait()
        n += 1
        if not stateful:
            continue
        for lb in sorted(lbs):
            if lb == 0 or lb not in reps:
                continue
            kinds = reps[lb]
            rows = [kinds.get("reuse", kinds.get("low"))] * B
            if B > 1 and "low" in kinds:
                rows[-1] = kinds["low"]
            for plans in ([RegionPlan(s) for s in rows],
                          [RegionPlan(kinds.get("low", rows[0]))] * B):
                sm.infer_wave(sm.stage_frames(frames[idx]), plans, beta,
                              caches=caches, frame_ids=list(range(B)),
                              capture_beta=beta, defer=True).wait()
                n += 1
    return n


# ---------------------------------------------------------------------------
# one run


def cell_metrics(bench: Dict, workload: str, kind: str):
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


class Cell:
    """A cell's files, weights, warmed program and traffic for one run."""

    def __init__(self, workload: str, bench_path: Path, traffic_dir: Path,
                 require_tpu: bool):
        configure_jax()
        import jax
        from . import traffic as T
        self.jax = jax
        self.devices = jax.devices()
        self.dev = self.devices[0]
        self.bench = load_json(bench_path)
        self.workload = workload
        self.cell, cfg_entry = find_cell(self.bench, workload)
        if require_tpu and self.dev.platform != "tpu":
            raise RunError(f"no TPU: JAX found only {self.dev.platform!r} "
                           f"devices ({self.dev.device_kind})")
        if len(self.devices) < self.cell["chips"]:
            raise RunError(f"{len(self.devices)} {self.dev.device_kind} "
                           f"devices; the cell asks for {self.cell['chips']}")
        log(f"device: {self.dev.platform} {self.dev.device_kind} "
            f"x{len(self.devices)}")
        self.counter = CompileCounter()
        conf = load_json(ROOT / cfg_entry["file"])
        self.model, self.serving = conf["model"], conf["serving"]
        self.traffic = T.load(traffic_dir / f"{self.cell['traffic']}.json")
        self.beta = self.traffic.get("beta", self.serving["beta"])
        self.top_k = self.serving["top_k"]
        self.sm = None

    def log(self, msg: str) -> None:
        log(f"{self.dev.device_kind}: {msg}")

    def build(self, seed: int) -> None:
        """Weights from the seed, the program's ServerModel, its warmup
        and the warm pass."""
        from repro.offload.simulator import ServerModel
        from . import traffic as T
        from . import weights as Wt
        t = time.perf_counter()
        params = Wt.make(self.model, seed32(seed), self.serving["dtype"])
        self.log(f"weights made on the device in "
                 f"{time.perf_counter() - t:.2f} s")
        buckets = tuple(self.traffic["batch_buckets"])
        quant = None
        if self.serving.get("quant"):
            from repro.quant.ptq import QuantSpec
            quant = QuantSpec(**self.serving["quant"])
        self.sm = sm = ServerModel(
            program_config(self.model), params, top_k=self.top_k,
            score_thresh=0.0, backend=self.serving["backend"],
            b_buckets=buckets, quant=quant)
        self.nR = sm.part.n_regions
        t = time.perf_counter()
        n_exec = sm.warmup(plan_space(self.traffic, self.nR, self.beta),
                           batch_buckets=buckets)
        self.lbs = sorted({k[0] for k in sm._fns})
        self.log(f"warmup {n_exec} executables (length buckets "
                 f"{self.lbs}) in {time.perf_counter() - t:.2f} s; "
                 f"persistent cache hits {self.counter.hits} misses "
                 f"{self.counter.misses}")
        self.frames = T.frame_pool(self.traffic, self.model["img_size"],
                                   seed)
        t = time.perf_counter()
        n_warm = warm_pass(sm, self.frames, self.traffic, self.beta,
                           self.lbs)
        self.log(f"warm pass {n_warm} waves in "
                 f"{time.perf_counter() - t:.2f} s")

    def reseed(self, seed: int) -> None:
        """New weights and frames from ``seed`` in the warmed program
        (calibration: the executables take the weights as arguments)."""
        from . import traffic as T
        from . import weights as Wt
        self.sm.params = Wt.make(self.model, seed32(seed),
                                 self.serving["dtype"])
        self.frames = T.frame_pool(self.traffic, self.model["img_size"],
                                   seed)

    def serve(self, seed: int, seconds: float, tick=None):
        """Ramp, then a window of ``seconds``.  Returns (sessions, loop
        result, window start, window end, compiles while serving)."""
        from . import traffic as T
        sessions = T.sessions(self.traffic, self.nR, seed)
        before = self.counter.compiles
        # what set-up made stays alive through the window: keep it out of
        # the cyclic collector, whose full collections would otherwise
        # walk it (every JAX object of the warmed grid) in the window
        gc.collect()
        gc.freeze()
        watch = GcPauses()
        gc.callbacks.append(watch)
        start = time.perf_counter()
        t0 = start + self.traffic["ramp_seconds"]
        t1 = t0 + seconds
        try:
            res = serve(self.sm, sessions, self.frames, self.traffic,
                        self.beta, start, t1,
                        tick=(lambda now: tick(now, t0)) if tick else None)
        finally:
            gc.callbacks.remove(watch)
            gc.unfreeze()
        res.gc_pauses = watch.pauses
        compiles = self.counter.compiles - before
        if compiles or self.sm.stats.steady_compiles:
            raise RunError(f"{compiles} compiles while serving (steady "
                           f"executables {self.sm.stats.steady_compile_keys})")
        return sessions, res, t0, t1

    def collect(self, res, seed: int):
        """Host copies of what the check compares: the served top-k of a
        seeded sample of offloads, and every session's cached tiles."""
        from . import check as C
        from . import reference as R
        dd = self.sm.part.windows_per_full_region
        nR = self.nR

        def n_windows(off):
            return (nR - off.n_low - off.n_reuse) * dd + off.n_low
        picked = C.sample_jobs(res.jobs, self.traffic["check_offloads"],
                               seed, n_windows)
        by_wave = {w.wid: w for w in res.waves}
        served = {}
        for j in picked:
            pw = by_wave[j.wave].pending
            served[(j.off.session, j.off.index)] = (
                np.asarray(pw.scores[j.row]), np.asarray(pw.boxes[j.row]))
        g = R.Geometry.from_config(self.model)
        tiles = {}
        if self.traffic["stateful"]:
            last: Dict[int, int] = {}
            for j in res.jobs:
                last[j.off.session] = max(last.get(j.off.session, -1),
                                          j.off.index)
            for sid, k in last.items():
                tiles[sid] = (k, R.tiles_to_grid(
                    np.asarray(res.caches[sid].tiles), g))
        return served, tiles

    def reference(self, seed: int, sessions, control: bool = False):
        from . import check as C
        from . import reference as R
        from . import weights as Wt
        params = Wt.make(self.model, seed32(seed), "float32")
        return C.Reference(params, R.Geometry.from_config(self.model),
                           self.beta, self.top_k, self.frames, sessions,
                           self.traffic["stateful"], control=control)


def _free(cell: "Cell", res) -> None:
    import gc
    for w in res.waves:
        w.pending = None
    res.caches = {}
    cell.sm = None
    gc.collect()


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, bench_path: Path = ROOT / "BENCHMARK.json",
             require_tpu: bool = True, limits: Optional[Dict] = None,
             traffic_dir: Path = BENCH_DIR / "traffic",
             hooks: Optional[Dict] = None) -> Dict:
    """Run one cell once and return its result line (a dict).

    ``hooks`` (tests only): ``{"after_setup": fn(sm)}`` plants a fault
    in the program after set-up.  ``limits`` overrides the cell's limit
    file.
    """
    from . import check as C
    cell = Cell(workload, bench_path, traffic_dir, require_tpu)
    jax, dev = cell.jax, cell.dev
    if limits is None:
        limits = load_json(BENCH_DIR / "limits" / f"{workload}.json")
    cell.build(seed)
    if hooks and "after_setup" in hooks:
        hooks["after_setup"](cell.sm)

    trace_s = min(seconds, TRACE_SECONDS)
    tr = {"on": False, "done": not trace, "lo": None, "hi": None}

    def tick(now, t0):
        if not tr["done"] and not tr["on"] and now >= t0:
            # device ops only: the host tracer slows every dispatch of
            # the loop several-fold and takes tens of seconds to stop, so
            # the loop's own spans are logged on the host clock and put
            # on the trace's, whose zero is the start of the trace
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            zero = time.perf_counter()
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            tr.update(on=True, zero=zero, lo=time.perf_counter())
        elif tr["on"] and now >= t0 + trace_s:
            stop()

    def stop():
        # the traced span ends where stop_trace starts: stopping blocks
        # the loop for seconds while the profiler collects the trace
        hi = time.perf_counter()
        jax.profiler.stop_trace()
        tr.update(on=False, done=True, hi=hi)

    sessions, res, t0, t1 = cell.serve(seed, seconds, tick)
    if tr["on"]:
        stop()
    setup_s = t0 - t_start
    e2e = end_to_end(res, t0, t1)
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    cell.log(f"{e2e['_completed']} offloads in the window, "
             f"{len(res.waves)} waves served, setup {setup_s:.2f} s")
    cell.log(timeline(res, t0, t1))
    served, tiles = cell.collect(res, seed)
    waves_meta = [(w.wid, len(w.jobs), w.lb, w.host_s, w.dispatch, w.done)
                  for w in res.waves]
    jobs, spans = res.jobs, res.spans
    _free(cell, res)

    t = time.perf_counter()
    numbers = C.compare(cell.reference(seed, sessions), served, tiles)
    cell.log(f"reference over {len(served)} offloads and {len(tiles)} "
             f"sessions' tiles in {time.perf_counter() - t:.2f} s")
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    metrics = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(cell.devices), "memory_peak_bytes": int(mem)}
    out = {"correct": bool(correct), "attempted": e2e["_completed"],
           "failed": 0}
    if not trace:
        values = {"setup_s": setup_s, **e2e}
        for m in cell_metrics(cell.bench, workload, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        from . import tracing as TR
        path = TR.latest_xplane(str(TRACE_DIR))
        if path is None:
            raise RunError("the profiler wrote no trace")
        dev_ev, _, layout = TR.read(path)
        if not dev_ev:
            raise RunError(f"trace holds no device ops: {layout}")
        lo, hi = 0.0, (tr["hi"] - tr["zero"]) * 1e9
        host_ev = TR.rebase(spans, tr["zero"], lo, hi)
        first = min(e[1] for e in dev_ev)
        last = max(e[2] for e in dev_ev)
        cell.log(f"trace: device ops from {first * 1e-9:.3f} s to "
                 f"{last * 1e-9:.3f} s of the traced {hi * 1e-9:.3f} s "
                 f"(start_trace took {tr['lo'] - tr['zero']:.4f} s)")
        if first < lo - 1e8 or last > hi + 1e9:
            # another clock: read the device's own extent, and name no gap
            cell.log("trace: device ops lie outside the traced span; "
                     "their own extent is the window, gaps go unnamed")
            lo, hi, host_ev = first, last, []
        device.update(busy_s=TR.busy_ns(dev_ev, lo, hi) * 1e-9,
                      window_s=(hi - lo) * 1e-9)
        ctx = {"jobs": jobs, "waves": waves_meta, "lo_s": tr["lo"],
               "hi_s": tr["hi"], "device_events": dev_ev,
               "host_spans": host_ev, "trace_lo_ns": lo,
               "trace_hi_ns": hi, "model": cell.model, "beta": cell.beta,
               "device_kind": dev.device_kind, "traffic": cell.traffic}
        for m in cell_metrics(cell.bench, workload, "per_layer"):
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = {
            "device_ops": TR.top_ops(dev_ev, lo, hi),
            "idle_gaps": TR.named_gaps(dev_ev, host_ev, lo, hi)}
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}: {c['value']:.6g} (limit {c['limit']:.6g})")
    return out


def calibrate(workload: str, seeds, seconds: float, *, bench_path: Path,
              traffic_dir: Path, require_tpu: bool = True,
              dump: Optional[Path] = None):
    """Program and control readings of the compared numbers, per seed,
    in one process (see calibrate.py).  The control is the reference
    computed in bfloat16, put in the program's place.  With ``dump``,
    the matched detections of each sampled offload go to
    ``<dump>/<workload>.<seed>.npz``."""
    from . import check as C
    cell = Cell(workload, bench_path, traffic_dir, require_tpu)

    def readings(seed, first):
        if first:
            cell.build(seed)
        else:
            cell.reseed(seed)
        sessions, res, t0, t1 = cell.serve(seed, seconds)
        done = sum(1 for j in res.jobs if t0 <= j.done <= t1)
        served, tiles = cell.collect(res, seed)
        for w in res.waves:
            w.pending = None
        res.caches = {}
        t = time.perf_counter()
        ref = cell.reference(seed, sessions)
        numbers = C.compare(ref, served, tiles)
        return ref, served, tiles, numbers, done, time.perf_counter() - t

    rows = []
    for i, seed in enumerate(seeds):
        ref, served, tiles, prog, done, t_ref = readings(seed, i == 0)
        ctrl = cell.reference(seed, ref.sessions.values(), control=True)
        c_served, c_tiles = C.control_outputs(
            ctrl, list(served), {s: k for s, (k, _) in tiles.items()})
        row = {"workload": workload, "seed": seed,
               "offloads_in_window": done, "program": prog,
               "control_numbers": C.compare(ref, c_served, c_tiles),
               "reference_s": t_ref, "kind": cell.dev.device_kind}
        rows.append(row)
        if dump is not None:
            _dump_detections(Path(dump) / f"{workload}.{seed}.npz",
                             served, ref, ctrl)
        cell.log(f"seed {seed}: program {prog} control "
                 f"{row['control_numbers']} (reference {t_ref:.1f} s)")
    return rows


def _dump_detections(path: Path, served: Dict, ref, ctrl) -> None:
    """Per sampled offload, for the program (``p_``) and the control
    (``c_``): each detection's score and box gap, and the reference's
    score and box at the matched location."""
    from . import check as C
    keys = sorted(served)
    arrays = {"keys": np.asarray(keys, np.int64)}
    for tag, get in (("p", lambda k: served[k]), ("c", ctrl.top)):
        rows = []
        for k in keys:
            s, b = get(k) if tag == "p" else get(*k)
            rs, rb = ref.detections(*k)
            i, gap = C.match(b, rb)
            rows.append((np.asarray(s, np.float64), gap, rs[i], rb[i]))
        for j, name in enumerate(("score", "gap", "ref_score", "ref_box")):
            arrays[f"{tag}_{name}"] = np.stack([r[j] for r in rows])
    path.parent.mkdir(parents=True, exist_ok=True)
    np.savez(path, **arrays)
