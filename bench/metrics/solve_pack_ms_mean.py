"""Mean host wall of ``solve.pack``, building the padded host arrays of a
jit solver launch (query masks, k, gamma, the matroid's arrays), in ms."""


def read(w):
    d = [s.dur_us for s in w.spans if s.name == "solve.pack"]
    return sum(d) / len(d) * 1e-3 if d else None
