"""A reader for the tests of the lookup: the gap of the first step's loss;
nothing to read where a run has no losses."""


def read(prog, ref):
    if not prog.losses or not ref.losses:
        return None
    return abs(prog.losses[0] - ref.losses[0])
