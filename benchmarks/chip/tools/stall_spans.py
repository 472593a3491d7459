#!/usr/bin/env python3
"""Where the serving loop's stalls lie: serve untraced windows of a cell
with the program's span recorder on, and print the span tree of every
wave that takes ``--stall-s`` or more from dispatch to detections.

    python benchmarks/chip/tools/stall_spans.py --workload <cell> \
        --seed <n> [--windows 12] [--off-windows 3] [--seconds 51] \
        [--trace-last 0] [--out <file.jsonl>]

from the root of a checkout, on the chip.  One process builds the cell
once (as ``run.py`` does), then serves ``--windows`` windows with the
recorder on and ``--off-windows`` with it off, each with its own seed
(``--seed`` + its index), the first ``2 * --off-windows`` alternating
off and on, so that ``offload_p50_ms`` with and without the recorder
compare windows served side by side.  ``--trace-last k`` also takes a
device-only profiler trace across the whole of the last k windows and,
for each stall in them, prints the device's busy share and heaviest ops
during the stall.

Each stall is put where its time went: the program span (of any wave)
with most time inside the stalled wave's interval, among ``serve.layout``,
``serve.tiles``, ``serve.args``, ``serve.launch``, ``serve.cache_refresh``
and ``serve.stage`` (host), ``serve.ready`` (waiting on the device) and
``serve.decode`` (host), or "between spans" for time in no program span.
One JSON line per window goes to ``--out``.
"""
import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import harness as H  # noqa: E402
from chipbench import program_spans as PS  # noqa: E402
from chipbench import tracing as TR  # noqa: E402

LEAVES = ("serve.stage", "serve.layout", "serve.tiles", "serve.args",
          "serve.launch", "serve.cache_refresh", "serve.ready",
          "serve.decode")


def attribute(spans, lo_ns: int, hi_ns: int):
    """Milliseconds of [lo_ns, hi_ns] in each leaf span name (any wave),
    and in no program span at all."""
    out = {}
    for name in LEAVES:
        iv = TR.clip(TR.merge((s.t0, s.t1) for s in spans
                              if s.name == name), lo_ns, hi_ns)
        ms = sum(e - s for s, e in iv) * 1e-6
        if ms:
            out[name] = ms
    covered = TR.clip(TR.merge((s.t0, s.t1) for s in spans
                               if not s.parent), lo_ns, hi_ns)
    out["between spans"] = ((hi_ns - lo_ns)
                            - sum(e - s for s, e in covered)) * 1e-6
    return out


def tree(spans, wave, t0_ns: int):
    """Lines of one program wave's span tree, times from ``t0_ns``."""
    mine = [s for s in spans if s.wave is wave]
    kids = {}
    for s in mine:
        kids.setdefault(s.parent, []).append(s)
    ids = {s.sid for s in mine}
    lines = []

    def walk(s, depth):
        counts = " ".join(f"{k}={n}" for k, n in sorted(s.counts.items()))
        lines.append(f"{'  ' * depth}{s.name} +{(s.t0 - t0_ns) * 1e-6:.2f}"
                     f" ms, {(s.t1 - s.t0) * 1e-6:.2f} ms {counts}")
        for c in kids.get(s.sid, []):
            walk(c, depth + 1)
    for s in mine:
        if s.parent not in ids:
            walk(s, 1)
    return lines


def program_wave(spans, w):
    """The program's wave whose ``serve.stage`` opened inside the loop's
    dispatch of wave ``w``."""
    lo, hi = w.dispatch * 1e9, (w.dispatch + w.host_s) * 1e9
    for s in spans:
        if s.name == "serve.stage" and lo <= s.t0 <= hi:
            return s.wave
    return None


def device_during(dev, lo, hi, top=5):
    busy = TR.busy_ns(dev, lo, hi)
    return {"busy_frac": busy / max(hi - lo, 1.0),
            "top_ops": TR.top_ops(dev, lo, hi, top)}


def breakdown(rec, dev, path, zero: float, lo: float, hi: float,
              frames: int):
    """A traced window's device time by scope and sub-scope, the busy
    time no scope covers, and the idle time by the program span it falls
    in (ms; on the trace's clock, [lo, hi] in ns)."""
    mods, _, _ = TR.read(path, device_line="XLA Modules")
    scoped = PS.op_scopes(dev, sorted((s, e, n.split("(")[0])
                                      for n, s, e, _ in mods))
    by = {}
    for sc in {sc for sc, _, _ in scoped}:
        top = sc.split("/")[0]
        sub = "block" if "/block" in sc else sc
        by.setdefault(top, {}).setdefault(sub, [])
        by[top][sub] += [(s, e) for x, s, e in scoped if x == sc]
    scope_ms = {top: {sub: sum(e - s for s, e in TR.clip(TR.merge(iv), lo,
                                                          hi)) * 1e-6
                      for sub, iv in subs.items()}
                for top, subs in by.items()}
    busy = TR.busy_ns(dev, lo, hi)
    covered = sum(e - s for s, e in TR.clip(TR.merge(
        (s, e) for _, s, e in scoped), lo, hi))
    idle = TR.gaps(dev, lo, hi)
    prog = PS.on_trace_clock(rec, zero)
    idle_by = {}
    for name in LEAVES:
        iv = TR.clip(TR.merge((s, e) for n, s, e in prog if n == name),
                     lo, hi)
        idle_by[name] = PS.overlap_ns(idle, iv) * 1e-6
    roots = TR.clip(TR.merge((s, e) for (n, s, e), sp in zip(prog, rec)
                             if not sp.parent), lo, hi)
    in_prog = PS.overlap_ns(idle, roots) * 1e-6
    return {"window_ms": (hi - lo) * 1e-6, "busy_ms": busy * 1e-6,
            "frames": frames, "scope_ms": scope_ms,
            "unscoped_busy_ms": (busy - covered) * 1e-6,
            "idle_ms": (hi - lo - busy) * 1e-6,
            "idle_in_program_spans_ms": in_prog,
            "idle_by_span_ms": idle_by}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--windows", type=int, default=12)
    ap.add_argument("--off-windows", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--stall-s", type=float, default=0.4)
    ap.add_argument("--trace-last", type=int, default=0)
    ap.add_argument("--out", default=str(H.TRACE_DIR / "stall_spans.jsonl"))
    # a rehearsal without the chip: another BENCHMARK.json and traffic
    ap.add_argument("--bench", default=str(H.ROOT / "BENCHMARK.json"))
    ap.add_argument("--traffic-dir", default=str(H.BENCH_DIR / "traffic"))
    ap.add_argument("--no-tpu", action="store_true")
    a = ap.parse_args(argv)
    try:
        cell = H.Cell(a.workload, Path(a.bench), Path(a.traffic_dir),
                      require_tpu=not a.no_tpu)
    except H.RunError as e:
        H.log(f"stall_spans: FAIL: {e}")
        return 1
    from repro import spans as S
    jax = cell.jax
    t = time.perf_counter()
    cell.build(a.seed)
    cell.log(f"set-up {time.perf_counter() - t:.1f} s")
    plan = []
    for i in range(a.off_windows):
        plan += [False, True]
    plan += [True] * (a.windows - a.off_windows)
    traced = set(range(len(plan) - a.trace_last, len(plan)))
    out = Path(a.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    p50 = {True: [], False: []}
    with out.open("w") as f:
        for i, on in enumerate(plan):
            seed = a.seed + i
            S.clear()
            if on:
                S.enable()
            zero = None
            if i in traced:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 0
                zero = time.perf_counter()
                jax.profiler.start_trace(str(H.TRACE_DIR),
                                         profiler_options=opts)
            try:
                _, res, t0, t1 = cell.serve(seed, a.seconds)
            finally:
                if zero is not None:
                    jax.profiler.stop_trace()
                S.disable()
            e2e = H.end_to_end(res, t0, t1)
            rec = S.recorded()
            dev = []
            if zero is not None:
                path = TR.latest_xplane(str(H.TRACE_DIR))
                dev, _, _ = TR.read(path)
            stalls = []
            for w in res.waves:
                if not (t0 <= w.dispatch <= t1 and w.done) or \
                        w.done - w.dispatch < a.stall_s:
                    continue
                lo, hi = int(w.dispatch * 1e9), int(w.done * 1e9)
                where = attribute(rec, lo, hi) if on else {}
                st = {"wave": w.wid, "size": len(w.jobs),
                      "at_s": w.dispatch - t0,
                      "ms": (w.done - w.dispatch) * 1e3,
                      "where_ms": where,
                      "held_by": max(where, key=where.get) if where
                      else None}
                pw = program_wave(rec, w) if on else None
                if pw is not None:
                    st["tree"] = tree(rec, pw, lo)
                if dev:
                    st["device"] = device_during(
                        dev, (w.dispatch - zero) * 1e9,
                        (w.done - zero) * 1e9)
                stalls.append(st)
            if zero is None:
                p50[on].append(e2e["offload_p50_ms"])
            if dev:
                frames = sum(1 for j in res.jobs if t0 <= j.done <= t1)
                bd = breakdown(rec, dev, path, zero, (t0 - zero) * 1e9,
                               (t1 - zero) * 1e9, frames)
                print(f"  breakdown {json.dumps(bd)}", flush=True)
            line = {"window": i, "seed": seed, "recorder": on,
                    "traced": zero is not None,
                    "offload_p50_ms": e2e["offload_p50_ms"],
                    "offload_p90_ms": e2e["offload_p90_ms"],
                    "frames_per_s": e2e["frames_per_s"],
                    "spans": len(rec), "stalls": stalls,
                    "breakdown": bd if dev else None,
                    "timeline": H.timeline(res, t0, t1)}
            f.write(json.dumps(line) + "\n")
            f.flush()
            print(f"window {i} seed {seed} recorder "
                  f"{'on' if on else 'off'}{' traced' if zero else ''}: "
                  f"p50 {e2e['offload_p50_ms']:.3f} ms, p90 "
                  f"{e2e['offload_p90_ms']:.1f} ms, "
                  f"{e2e['frames_per_s']:.2f} frames/s, {len(rec)} spans, "
                  f"{len(stalls)} waves >= {a.stall_s} s", flush=True)
            print(f"  {line['timeline']}", flush=True)
            for st in stalls:
                where = ", ".join(f"{k} {v:.1f}" for k, v in sorted(
                    st["where_ms"].items(), key=lambda kv: -kv[1]))
                print(f"  stall: wave {st['wave']} of {st['size']} at "
                      f"{st['at_s']:.2f} s took {st['ms']:.1f} ms; held by "
                      f"{st['held_by']} (ms: {where})", flush=True)
                for ln in st.get("tree", []):
                    print(f"   {ln}", flush=True)
                if "device" in st:
                    print(f"   device busy "
                          f"{100 * st['device']['busy_frac']:.1f} %; "
                          f"{st['device']['top_ops']}", flush=True)
            for w in res.waves:
                w.pending = None
            res.caches = {}
    maps = S.scope_map()
    print(f"scope map: {len(maps)} modules, "
          f"{sum(len(m) for m in maps.values())} scoped instructions",
          flush=True)
    for on in (False, True):
        if p50[on]:
            print(f"recorder {'on' if on else 'off'}: offload_p50_ms "
                  f"{[round(v, 3) for v in p50[on]]}, median "
                  f"{statistics.median(p50[on]):.3f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
