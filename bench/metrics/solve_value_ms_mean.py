"""Mean host wall of ``solve.value``, the float64 ``selection_value`` of
each answer of a launch, in ms."""


def read(w):
    d = [s.dur_us for s in w.spans if s.name == "solve.value"]
    return sum(d) / len(d) * 1e-3 if d else None
