"""One of the program's counters over another, both as they grew in the
window, in percent. ``None`` where the program counts neither (a commit from
before the counter existed) or the denominator did not move: the metric is
then left out."""


def read(args, facts):
    counters = facts["counters"]
    part, whole = counters.get(args["numerator"]), counters.get(args["denominator"])
    if part is None or not whole:
        return None
    return 100.0 * part / whole
