"""Bytes handed from the host to the device per solver launch, in MB
(1e6 bytes): the ``bytes`` of the window's ``solve.h2d`` spans summed,
over the number of ``solve`` spans."""


def read(w):
    b = [s.args["bytes"] for s in w.spans if s.name == "solve.h2d"]
    n = sum(1 for s in w.spans if s.name == "solve")
    return sum(b) / n * 1e-6 if b and n else None
