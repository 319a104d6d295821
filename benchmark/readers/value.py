"""A reading the harness took itself (host clock around a phase, the
compiler's memory analysis, the trace's totals), by its key."""


def read(args, facts):
    return facts["values"].get(args["key"])
