"""The back end's part of warm-up (`warmup_compile`): XLA compiling the
programs, or loading them from the persistent cache where it hits."""
from benchmark.startup import startup


def read(observed):
    up = startup(observed)
    return None if up is None else up["warmup_compile"]
