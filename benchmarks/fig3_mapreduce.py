"""Paper Fig. 3: MRCoreset scalability with parallelism l = 1, 2, 4, 8
(each l runs in a subprocess with that many forced host devices, mirroring
the paper's 1..16-machine Spark sweep), vs SeqCoreset and StreamCoreset at
the same tau.

The children emulate l machines on the CPU backend (``JAX_PLATFORMS=cpu``,
rows labelled ``platform=cpu``): an accelerator belongs to one process, and
the parent that runs the other suites may already hold it.

Container scale: n=20000, tau=64, k=8.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from .common import csv_line

_CHILD = """
import json, numpy as np, jax
import sys
sys.path.insert(0, {src!r})
from benchmarks.common import songs_like, wikipedia_like, Timer
from repro.core import solve_dmmc
from repro.launch.mesh import make_mesh

n, k, tau, l, ds = {n}, {k}, {tau}, {l}, {ds!r}
P, cats, caps, spec = (songs_like if ds == "songs" else wikipedia_like)(n)
mesh = make_mesh((l,), ("data",))
with Timer() as t:
    sol = solve_dmmc(P, k, spec, cats=cats, caps=caps, tau=tau,
                     setting="mapreduce", mesh=mesh, metric="cosine")
# per-shard construction latency: one reducer's work (n/l points,
# tau/l centers) — the wall-clock a real l-machine round takes (this
# container has ONE core, so the mapreduce timing above measures
# aggregate work, not parallel latency)
sol1 = solve_dmmc(P[: n // l], k, spec, cats=cats[: n // l], caps=caps,
                  tau=max(1, tau // l), setting="sequential",
                  metric="cosine")
with Timer() as t1:
    sol1 = solve_dmmc(P[: n // l], k, spec, cats=cats[: n // l],
                      caps=caps, tau=max(1, tau // l),
                      setting="sequential", metric="cosine")
print(json.dumps(dict(time_s=t.s, diversity=sol.diversity,
                      coreset=sol.coreset_size,
                      coreset_s=sol.timings["coreset_s"],
                      per_shard_s=sol1.timings["coreset_s"],
                      solver_s=sol.timings["solver_s"])))
"""


def run(n=20000, k=8, tau=64, quick=False):
    src = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rows = []
    ells = (1, 4) if quick else (1, 2, 4, 8)
    for ds in ("songs", "wikipedia"):
        for l in ells:
            code = _CHILD.format(src=src, n=n, k=k, tau=tau, l=l, ds=ds)
            env = dict(os.environ)
            env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={l}"
            env["PYTHONPATH"] = os.path.join(src, "src")
            env["JAX_PLATFORMS"] = "cpu"
            r = subprocess.run([sys.executable, "-c", code],
                               capture_output=True, text=True, env=env,
                               timeout=1800)
            assert r.returncode == 0, r.stderr[-2000:]
            rec = json.loads(r.stdout.strip().splitlines()[-1])
            rec.update(dataset=ds, l=l, platform="cpu")
            rows.append(rec)
    return rows


def main(quick=False):
    rows = run(quick=quick)
    best = {}
    for r in rows:
        best[r["dataset"]] = max(best.get(r["dataset"], 0), r["diversity"])
    return [
        csv_line(
            f"fig3_{r['dataset']}/l={r['l']}", r["time_s"] * 1e6,
            f"platform={r['platform']};"
            f"diversity_ratio={r['diversity']/best[r['dataset']]:.4f};"
            f"coreset_s={r['coreset_s']:.2f};"
            f"per_shard_s={r['per_shard_s']:.2f};"
            f"solver_s={r['solver_s']:.2f}",
        )
        for r in rows
    ]


if __name__ == "__main__":
    print("\n".join(main()))
