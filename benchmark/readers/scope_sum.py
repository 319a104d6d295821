"""Device ms per iteration of the leaf events under the named lgbtpu scopes
(outermost scope of each op), or under none of them (``unscoped``)."""


def read(args, facts):
    trace = facts.get("trace")
    if not trace or not facts["trace_iters"]:
        return None
    by = trace["by_scope"]
    if args.get("unscoped"):
        seconds = by.get("", 0.0)
    else:
        seconds = sum(by.get(s, 0.0) for s in args["scopes"])
    return 1e3 * seconds / facts["trace_iters"]
