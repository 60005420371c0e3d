"""Shared by the readers of the program's own spans and compile counter
(``repro.common.telemetry``): per-round milliseconds over the window.

A reader gets None where the program has no recorder, where none of the
spans it reads started in the window, or where the recorder dropped
records from the window."""


def recorder():
    """The program's telemetry module, or None where it has none."""
    try:
        from repro.common import telemetry
    except ImportError:
        return None
    return telemetry


def _window(ctx):
    t = recorder()
    if t is None or not ctx.get("rounds"):
        return None
    lo, hi = ctx["window"]
    return (t, lo, hi) if t.complete(lo) else None


def per_round_ms(ctx, add, less=()):
    """Seconds in the spans ``add`` less those in ``less`` (all starting in
    the window), per round, in ms."""
    w = _window(ctx)
    if w is None:
        return None
    t, lo, hi = w
    added = [t.total(n, lo, hi) for n in add]
    if not any(n for n, _ in added):
        return None
    secs = sum(s for _, s in added) - sum(t.total(n, lo, hi)[1] for n in less)
    return secs / ctx["rounds"] * 1e3


def compile_ms(ctx):
    """Compile seconds charged to any span in the window, per round, in ms;
    None where no ``round`` span started in it."""
    w = _window(ctx)
    if w is None:
        return None
    t, lo, hi = w
    if not t.total("round", lo, hi)[0]:
        return None
    return sum(s for _, s in t.compiles(lo, hi).values()) / ctx["rounds"] * 1e3
