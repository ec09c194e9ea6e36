"""Local executor: the profile's execute phase, per statement."""

import statistics


def read(run):
    values = [st.profile.phases["execute"] for st in run.done
              if st.profile is not None and "execute" in st.profile.phases]
    return statistics.median(values) if values else None
