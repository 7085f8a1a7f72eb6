"""setup_s: seconds from the process's start to the window's first offer
(imports, device start, weights from the seed, engine build, warm-up and any
compiling), on the host clock."""


def read(art):
    return art["setup_s"]
