"""Device time of collective operations per round: the union of the
intervals of all-gathers, all-reduces, reduce-scatters,
collective-permutes and all-to-alls in the traced window, averaged over
the chips (``trace_reduce``'s ``collective_s``), over the window's
rounds. Both the exchange between workers and the tensor-parallel
collectives inside a worker's solve count."""


def read(rec):
    t = rec["trace"]
    if not t.get("collective_s"):
        return None
    return 1e3 * t["collective_s"] / rec["rounds"]
