"""Window delta of one of the program's counters."""


def read(args, facts):
    return facts["counters"].get(args["counter"])
