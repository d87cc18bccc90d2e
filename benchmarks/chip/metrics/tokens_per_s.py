"""Stream tokens of every request answered inside the window, over the
window's seconds (host clock).  A token is one initiation of the
fabric: one value on every input arc."""


def read(run):
    log = run.log
    if run.mode != "backlog" or log.window_s <= 0:
        return None
    tokens = sum(int(run.traffic.lengths[log.pool[u]])
                 for u, t in log.done.items() if t <= log.window_s)
    return tokens / log.window_s
