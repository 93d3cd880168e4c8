"""Local-search sweeps per solver launch: the ``sweeps`` of the window's
``solve.run`` spans (the trips of each launch's sweep loop, the most any
row of it made; stacked lanes summed) summed, over the number of ``solve``
spans. A program whose ``solve.run`` carries no ``sweeps`` reads nothing."""


def read(w):
    s = [s.args["sweeps"] for s in w.spans
         if s.name == "solve.run" and "sweeps" in s.args]
    n = sum(1 for s in w.spans if s.name == "solve")
    return sum(s) / n if s and n else None
