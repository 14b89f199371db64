"""Set-up seconds: process start to the window's opening (imports, chip
start, graph generation, ``load_graph``, and the warm-up that compiles
or loads every program the window runs)."""


def read(run):
    return run.setup_s
