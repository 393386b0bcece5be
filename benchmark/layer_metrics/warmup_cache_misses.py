"""Programs that warm-up compiled and wrote to the persistent cache instead
of finding them there (`warmup_cache["misses"]`): 0 in a warm start."""
from benchmark.startup import startup


def read(observed):
    cache = startup(observed, "warmup_cache")
    return None if cache is None else cache["misses"]
