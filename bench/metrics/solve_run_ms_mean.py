"""Mean host wall of ``solve.run``, a launch from the call into the jitted
solver until its answers are numpy arrays: dispatch, the rest of the
transfer, the device run and the copy back, in ms."""


def read(w):
    d = [s.dur_us for s in w.spans if s.name == "solve.run"]
    return sum(d) / len(d) * 1e-3 if d else None
