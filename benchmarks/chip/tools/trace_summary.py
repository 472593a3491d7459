"""Print the layout of the newest benchmark trace and its heaviest device
operations with their stats, to name kernels for the per-layer readers.

    python benchmarks/chip/tools/trace_summary.py [trace dir]
"""
import sys
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import harness, tracing as TR  # noqa: E402

path = TR.latest_xplane(sys.argv[1] if len(sys.argv) > 1
                        else str(harness.TRACE_DIR))
dev, host, layout = TR.read(path)
for plane, lines in layout.items():
    print("plane", plane, lines[:12])
tot, cnt, ex = defaultdict(float), defaultdict(int), {}
for ev in dev:
    tot[ev[0]] += ev[2] - ev[1]
    cnt[ev[0]] += 1
    ex.setdefault(ev[0], ev[3])
for name in sorted(tot, key=lambda n: -tot[n])[:40]:
    st = {k: (v if not isinstance(v, str) else v[:160])
          for k, v in ex[name].items()}
    print(f"{tot[name] * 1e-6:10.2f} ms {cnt[name]:6d}  {name}  {st}")
print("host spans", sorted({h[0] for h in host}))
print("first device op", min(e[1] for e in dev), "first span",
      min(h[1] for h in host), "last", max(e[2] for e in dev),
      max(h[2] for h in host))
