"""boosting_loop (boosting/gbdt.py DART): trees the traced iterations
replayed to drop them, over the traced iterations: the program's own
counter `dart.trees_replayed`. It says which part of DART's schedule a
trace saw (the rounds that drop take ~0.1 x the trees grown, half the
rounds skip), so a faster change, traced on an older forest, is read
beside it. None on a program without the counter."""


def read(ev):
    if not ev.traced or ev.traced["counters"].get(
            "dart_trees_replayed") is None:
        return None
    return ev.traced["counters"]["dart_trees_replayed"] \
        / ev.traced["units"]["iters"]
