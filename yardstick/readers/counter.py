"""The increase of one series over the window. A series the program has
not touched yet is not exposed, and reads 0."""


def read(facts, args):
    return facts["counters"].get(args["series"], 0.0)
