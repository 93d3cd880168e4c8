"""Mean host wall of ``solve.h2d``, handing a launch's host arrays to the
device (``jnp.asarray``; a copy still in flight when it returns is timed
by ``solve.run``), in ms."""


def read(w):
    d = [s.dur_us for s in w.spans if s.name == "solve.h2d"]
    return sum(d) / len(d) * 1e-3 if d else None
