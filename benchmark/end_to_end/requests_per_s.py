"""Acknowledged closed-loop requests per second of window."""


def read(run):
    acked = [
        len(g["acked"]) for g in run.log.values()
        if isinstance(g, dict) and g.get("kind") == "closed_loop"
    ]
    return sum(acked) / (run.window[1] - run.window[0]) if acked else None
