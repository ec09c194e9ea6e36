"""Local executor (scan): bytes sent host to device, per statement."""


def read(run):
    sent = [st.profile.transfer_bytes for st in run.done
            if st.profile is not None]
    return sum(sent) / len(sent) / 1e6 if sent else None
