"""device_idle_share.leader: share of the traced window in which no
operation ran on the chip, in the leader cells (benchmark/trace.py)."""


def read(reading):
    red = reading.get("trace")
    if not red or red.get("idle_share") is None:
        return None
    return 100.0 * red["idle_share"]
