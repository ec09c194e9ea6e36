"""What the readers share.

The ten span metrics: the median over the window's answered statements
of a value read off each statement's span tree (``QueryProfile.spans``,
kept by the program since PR 26).

The two per-statement device metrics: how much of each statement ran in
the traced window. The trace is a few seconds of a longer window, so a
statement can begin before it, end after it, or outlast it altogether;
each counts by the share of its own time that the window holds."""

import statistics


def median_per_statement(run, value):
    """``value(profile)`` per answered statement, its median; None where
    the program keeps no span tree in its profiles (nothing to read)."""
    profiles = [st.profile for st in run.done if st.profile is not None
                and hasattr(st.profile, "spans")]
    if not profiles:
        return None
    return statistics.median(value(p) for p in profiles)


def share(st, t0, t1):
    """The part of the statement's own time, ``wall0`` to ``wall1``,
    that lies inside [t0, t1] on the same wall clock: 1 for a statement
    the window holds whole, 4/86 for an 86 s statement around a 4 s
    window, 0 for one that ran outside it."""
    overlap = min(st.wall1, t1) - max(st.wall0, t0)
    if overlap < 0:
        return 0.0
    length = st.wall1 - st.wall0
    return overlap / length if length > 0 else 1.0


def shares_in_window(run, statements):
    """[(statement, share)] of the ``statements`` that ran in the traced
    window (``run.trace["wall"]``), every stream's alike. The one count
    of "statements in the window": the sum of the shares."""
    t0, t1 = run.trace["wall"]
    return [(st, s) for st in statements if (s := share(st, t0, t1)) > 0]
