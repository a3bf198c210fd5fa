"""Reader ``cpu_share``: process CPU seconds over wall seconds of the
window, in per cent of one core. ``who``: ``clients`` (mean over the load
generator children, ``time.process_time()``) or ``silo`` (the parent, all
its threads)."""


def read(ctx: dict, who: str):
    rows = ctx["cpu"].get(who) or []
    shares = [r["cpu_s"] / r["wall_s"] for r in rows if r["wall_s"] > 0]
    return 100.0 * sum(shares) / len(shares) if shares else None
