"""engine_warm_s: the time of EngineRegistry.acquire(..., warm=True)
for the cell's one engine: trace, lowering, and a compile or a load
from the persistent cache."""


def read(reading):
    return reading.get("engine_warm_s")
