"""Process start to the first timed request: data, table build, upload,
compilation and warm-up (host clock)."""


def read(w):
    return w.setup_s
