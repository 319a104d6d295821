"""Share (%) of the traced call's attributed idle seconds that no program
span names. ``facts["trace"]["idle_gaps"]`` maps the innermost host
annotation around each of the longest idle gaps to its seconds
(``trace_reduce.reduce_events``). Unnamed are the gaps under the benchmark's
own annotation (``bench/*``: the program had no span there) or under none;
the remainder of gaps too short to attribute is left out of both sides.
``None`` without a trace or without attributed gaps."""


def read(args, facts):
    trace = facts.get("trace")
    gaps = trace.get("idle_gaps") if trace else None
    if not gaps:
        return None
    left_out = set(args.get("left_out", []))
    unnamed_names = set(args.get("unnamed", []))
    prefixes = tuple(args.get("unnamed_prefixes", []))
    attributed = {k: v for k, v in gaps.items() if k not in left_out}
    total = sum(attributed.values())
    if total <= 0:
        return None
    unnamed = sum(v for k, v in attributed.items()
                  if k in unnamed_names or (prefixes and k.startswith(prefixes)))
    return 100.0 * unnamed / total
