"""Quickstart: diversity maximization under a partition matroid, all three
settings (sequential Alg. 1 / streaming Alg. 2 / MapReduce shard_map).

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax

from repro.core import PartitionMatroid, solve_dmmc
from repro.core.matroid import MatroidSpec


def main():
    rng = np.random.default_rng(0)
    n, h, k = 5000, 6, 8

    # points on a low-dimensional manifold (the paper's doubling-dimension
    # regime), each with a category; at most 2 picks per category allowed
    base = rng.normal(size=(n, 3)) @ rng.normal(size=(3, 16))
    points = (base + 0.05 * rng.normal(size=(n, 16))).astype(np.float32)
    cats = rng.integers(0, h, (n, 1)).astype(np.int32)
    caps = np.full(h, 2, np.int32)
    spec = MatroidSpec("partition", num_categories=h, gamma=1)

    for setting in ("sequential", "streaming", "mapreduce"):
        kw = dict(setting=setting, tau=64)
        if setting == "mapreduce":
            # every mesh in the repo is built by launch.mesh.make_mesh
            from repro.launch.mesh import make_mesh

            kw["mesh"] = make_mesh((len(jax.devices()),), ("data",))
        sol = solve_dmmc(points, k, spec, cats=cats, caps=caps, **kw)
        m = PartitionMatroid(cats[:, 0], caps)
        assert m.is_independent(list(sol.indices))
        print(f"{setting:>11}: diversity={sol.diversity:9.2f}  "
              f"coreset={sol.coreset_size:4d}/{n}  "
              f"coreset_time={sol.timings['coreset_s']:.2f}s  "
              f"solver_time={sol.timings['solver_s']:.2f}s  "
              f"picked={sorted(sol.indices.tolist())}")


if __name__ == "__main__":
    main()
