"""95th percentile of the latency of every read sent in the window and
answered, from just before its submit until the client holds its answer
(host clock)."""

from bench.reduce import percentile


def read(w):
    if not w.reads:
        return None
    return percentile([r.t_ready - r.t_submit for r in w.reads], 95) * 1e3
