"""setup_s: process start to window open (host clock): weights, engine,
compiles or compile-cache reads, warm-up and the first admissions."""


def read(w):
    return w.run.setup_s
