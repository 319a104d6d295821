"""One of the program's counters over another, both as they grew in the
window, as a plain quotient (``counter_ratio`` gives percent). ``None``
where the program counts neither or the denominator did not move."""


def read(args, facts):
    counters = facts["counters"]
    part, whole = counters.get(args["numerator"]), counters.get(args["denominator"])
    if part is None or not whole:
        return None
    return part / whole
