"""Entry points: what the wire adds around the server's own work."""

import statistics


def read(run):
    gaps = [st.ms - (st.profile.end_time - st.profile.start_time) * 1000.0
            for st in run.done if st.profile is not None]
    return statistics.median(gaps) if gaps else None
