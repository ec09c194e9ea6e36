"""Session / planner: parse + resolve + optimize, per statement."""

import statistics

PHASES = ("parse", "resolve", "optimize")


def read(run):
    values = [sum(st.profile.phases.get(p, 0.0) for p in PHASES)
              for st in run.done if st.profile is not None]
    return statistics.median(values) if values else None
