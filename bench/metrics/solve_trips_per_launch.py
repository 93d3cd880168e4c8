"""Local-search block-loop trips per solver launch: the ``trips`` of the
window's ``solve.run`` spans (the trips of each launch's candidate block
loop over all its sweeps, the most any row made; stacked lanes summed)
summed, over the number of ``solve`` spans. Visits over trips is how many
candidates a trip tests. A program whose ``solve.run`` carries no
``trips`` reads nothing."""


def read(w):
    t = [s.args["trips"] for s in w.spans
         if s.name == "solve.run" and "trips" in s.args]
    n = sum(1 for s in w.spans if s.name == "solve")
    return sum(t) / n if t and n else None
