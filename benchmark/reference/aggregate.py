"""The metric-aggregator's semantics as a dict replay. Imports nothing of
the program.

``send_metric(name, tag, value)`` folds ``value`` into the aggregator of
``name`` and into the aggregator of ``name.tag``. Values are small
integers carried as floats, so a sum is exact in any order.
"""


class Stats:
    __slots__ = ("count", "total", "vmin", "vmax")

    def __init__(self) -> None:
        self.count, self.total, self.vmin, self.vmax = 0, 0.0, 0.0, 0.0

    def fold(self, value: float) -> None:
        self.vmin = value if self.count == 0 else min(self.vmin, value)
        self.vmax = value if self.count == 0 else max(self.vmax, value)
        self.count += 1
        self.total += value

    def row(self) -> tuple:
        return (self.count, self.total, self.vmin, self.vmax)


def replay(acked) -> dict:
    """``{actor_id: Stats}`` after every acknowledged ``(name, tag, value)``."""
    out: dict = {}
    for name, tag, value in acked:
        out.setdefault(name, Stats()).fold(value)
        out.setdefault(f"{name}.{tag}", Stats()).fold(value)
    return out


def mismatches(acked, failed, stored: dict) -> int:
    """Actors whose stored ``(count, total, vmin, vmax)`` is not what the
    acknowledged requests make it. A request that failed may or may not
    have been applied, to the name, to the name's tag, or to both: an
    actor it touches is held to every value between the two.
    """
    want = replay(acked)
    maybe: dict = {}
    for name, tag, value in failed:
        maybe.setdefault(name, []).append(value)
        maybe.setdefault(f"{name}.{tag}", []).append(value)
    bad = 0
    for actor in set(want) | set(maybe):
        base = want[actor].row() if actor in want else (0, 0.0, 0.0, 0.0)
        got = stored.get(actor, (0, 0.0, 0.0, 0.0))
        extra = maybe.get(actor)
        if extra is None:
            bad += got != base
            continue
        ok = (
            base[0] <= got[0] <= base[0] + len(extra)
            and base[1] <= got[1] <= base[1] + sum(extra)
            and (base[0] == 0 or (got[2] <= base[2] and got[3] >= base[3]))
        )
        bad += not ok
    return bad
