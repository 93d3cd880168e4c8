#!/usr/bin/env python3
"""Readings that set the limits of a transversal cell's checks
(``bench/checks/wiki-restored-query.json``): ``bench/control.py`` run
as it is, with the transversal comparisons (``checks_transversal`` and the
reference local search of ``reference/transversal.py``) in the place of
the partition ones. The control matrices are ``control.cross``'s:

    python3 bench/control_transversal.py --workload wiki-restored-query \\
        --seeds 11,12,13 --seconds 10

Prints one JSON line per seed, as ``control.py`` does. The benchmark's own
runs never run this.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(HERE), "src"), HERE]

import control  # noqa: E402


def control_numbers(dep, ran, seed: int, step: str = "high",
                    n_sample: int = 0) -> dict:
    """The query numbers with the references at ``step`` in the program's
    place, over the answers (or a sample of ``n_sample``) of every query
    stream of the run."""
    import numpy as np

    import checks
    import checks_transversal
    from reference import local_search

    cat = dep.catalog
    h = int(dep.cfg["matroid"]["num_categories"])
    X = np.asarray(cat.rows, np.float64)
    D_ref = local_search.pdist(X)
    sq = (X * X).sum(axis=1)
    D_low = np.sqrt(np.maximum(
        sq[:, None] + sq[None, :] - 2.0 * control.cross(X, step), 0.0))
    np.fill_diagonal(D_low, 0.0)
    out = {"pdist_err": checks.pdist_err(D_low, cat.src, cat, D_ref)}
    for _kind, _p, res in ran:
        picked = control.sample(res.served, seed, n_sample)
        out.update(checks_transversal.answer_numbers(picked, cat, D_ref, h,
                                                     D_solve=D_low))
    return out


def main(argv=None) -> int:
    control.control_numbers = control_numbers
    return control.main(argv)


if __name__ == "__main__":
    sys.exit(main())
