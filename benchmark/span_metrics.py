"""What the ten span metrics' readers share: the median over the window's
answered statements of a value read off each statement's span tree
(``QueryProfile.spans``, kept by the program since PR 26)."""

import statistics


def median_per_statement(run, value):
    """``value(profile)`` per answered statement, its median; None where
    the program keeps no span tree in its profiles (nothing to read)."""
    profiles = [st.profile for st in run.done if st.profile is not None
                and hasattr(st.profile, "spans")]
    if not profiles:
        return None
    return statistics.median(value(p) for p in profiles)
