"""Window delta of the program's host-clock timers, per iteration, in ms."""


def read(args, facts):
    found = [facts["timers"][t] for t in args["timers"] if t in facts["timers"]]
    if not found or not facts["iters"]:
        return None
    return 1e3 * sum(found) / facts["iters"]
