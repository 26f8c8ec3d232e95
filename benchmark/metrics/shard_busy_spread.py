"""shard_busy_spread: the busiest chip's busy share of the traced window
minus the least busy chip's, in points of the window
(runners/offload_mesh.per_plane): how unevenly the sharded engine loads
the chips of one host."""


def read(reading):
    planes = reading.get("planes")
    if not planes or len(planes) < 2:
        return None
    busy = [p["busy_share"] for p in planes.values()]
    return 100.0 * (max(busy) - min(busy))
