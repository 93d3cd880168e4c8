"""Local-search swaps per solver launch: the ``swaps`` of the window's
``solve.run`` spans summed, over the number of ``solve`` spans. Each swap
recomputes the selection's row sums, one read of the whole distance
matrix; a program whose ``solve.run`` carries no ``swaps`` reads nothing."""


def read(w):
    s = [s.args["swaps"] for s in w.spans
         if s.name == "solve.run" and "swaps" in s.args]
    n = sum(1 for s in w.spans if s.name == "solve")
    return sum(s) / n if s and n else None
