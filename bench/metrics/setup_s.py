"""Set-up seconds: from process start to the window's opening (imports, graph,
block file, engine or server, warm-up; compiles in a cold run)."""


def read(r):
    return r.setup_s
