"""Process start to the first operation of the warm phase done: owner
start, bucket warm-up, nodes, preload."""


def read(w):
    return w.setup_s
