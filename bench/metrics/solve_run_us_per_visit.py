"""Host wall of ``solve.run`` per candidate visit of the local search, in
us: the summed duration of the window's ``solve.run`` spans that carry
``visits`` (rows x coreset rows x sweeps of the launch), over the summed
``visits``. It tells the cost of one visit (the matroid's swap test, the
swap value) from how many visits a launch makes. A program whose
``solve.run`` carries no ``visits`` reads nothing."""


def read(w):
    runs = [(s.dur_us, s.args["visits"]) for s in w.spans
            if s.name == "solve.run" and "visits" in s.args]
    visits = sum(v for _d, v in runs)
    return sum(d for d, _v in runs) / visits if visits else None
