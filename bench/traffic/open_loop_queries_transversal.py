"""Traffic kind ``open_loop_queries_transversal``: the open-loop diversity
queries of ``open_loop_queries`` (its ``schedule``, ``warm`` and ``drive``,
with the same parameters), over a service whose matroid is transversal,
judged by ``checks_transversal``. The drive's ``info`` adds the coreset
size the window served (``m``) and how many answers each engine gave
(``engines``).
"""
from __future__ import annotations

import collections

import checks_transversal
from harness import load_module

base = load_module("traffic", "open_loop_queries")
schedule = base.schedule
warm = base.warm


def drive(dep, p: dict, seed: int, seconds: float):
    res = base.drive(dep, p, seed, seconds)
    engines = collections.Counter(s.result.engine for s in res.served
                                  if s.result is not None)
    res.info.update(m=int(dep.svc.runtime.latest().size),
                    engines=dict(sorted(engines.items())))
    return res


def check(dep, p: dict, res, seed: int) -> dict:
    return checks_transversal.query_numbers(
        dep.svc, dep.catalog, res.served,
        int(dep.cfg["matroid"]["num_categories"]))
