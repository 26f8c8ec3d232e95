"""device_idle_share.dp4_max: the idle share of the idlest chip, in the
four-chip offload cell: 1 - that chip's busy share of the traced window
(runners/offload_mesh.per_plane). A chip that lags the others shows
here where the mean over the chips hides it."""


def read(reading):
    planes = reading.get("planes")
    if not planes:
        return None
    return 100.0 * max(1.0 - p["busy_share"] for p in planes.values())
