#!/usr/bin/env python3
"""Time one call of the flash attention kernel per (bq, bk) block pair.

    python3 benchmarks/flash_blocks.py --case 1:4096:16:64:float32 \
        [--case ...] [--bq 128,256,512,1024] [--bk 128,256,512,1024,2048] \
        [--reps 25] [--out PATH]

Each ``--case B:T:H:Dh:dtype`` times non-causal ``ops.flash_attention`` on
q, k and v of shape (B, T, H, Dh) at every (bq, bk) pair, and once with
no blocks given (the shape's default, ``block``).  Per pair: compile, two
warm calls, then ``--reps`` calls each ended by ``block_until_ready`` on
the host clock; the median and quartiles in ms go to stdout, one JSON
object per line, and are appended to ``--out`` when it is given.  A pair
the compiler refuses is recorded with its error.  Run on the chip with
``REPRO_AUTOTUNE=0``, so that ``block`` is the shape rule; it exits 1
without a TPU.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.kernels.flash_attention import ops  # noqa: E402


def _ints(s: str) -> list:
    return [int(x) for x in s.split(",")]


def time_call(fn, reps: int) -> list:
    for _ in range(2):
        fn().block_until_ready()
    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn().block_until_ready()
        ms.append((time.perf_counter() - t0) * 1e3)
    return ms


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--case", action="append", required=True,
                    help="B:T:H:Dh:dtype, e.g. 1:4096:16:64:float32")
    ap.add_argument("--bq", type=_ints, default=[128, 256, 512, 1024])
    ap.add_argument("--bk", type=_ints, default=[128, 256, 512, 1024, 2048])
    ap.add_argument("--reps", type=int, default=25)
    ap.add_argument("--out", type=Path)
    a = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("flash_blocks: no TPU", file=sys.stderr)
        return 1
    kind = jax.devices()[0].device_kind
    rows = []
    for case in a.case:
        *dims, dt = case.split(":")
        B, T, H, Dh = map(int, dims)
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(key, (B, T, H, Dh), jnp.dtype(dt))
                   for key in keys)
        pairs = [(None, None)] + [(x, y) for x in a.bq for y in a.bk]
        for bq, bk in pairs:
            row = {"device": kind, "B": B, "T": T, "H": H, "Dh": Dh,
                   "dtype": dt, "bq": bq or "block", "bk": bk or "block"}
            try:
                ms = time_call(lambda: ops.flash_attention(
                    q, k, v, bq=bq, bk=bk), a.reps)
                q1, _, q3 = statistics.quantiles(ms, n=4)
                row.update(ms_p50=statistics.median(ms), ms_q1=q1,
                           ms_q3=q3, reps=len(ms))
            except Exception as e:       # the compiler refused the pair
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(json.dumps(row), flush=True)
            rows.append(row)
    if a.out:
        a.out.parent.mkdir(parents=True, exist_ok=True)
        with a.out.open("a") as f:
            f.writelines(json.dumps(row) + "\n" for row in rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
