"""Making the weights (`init_params`) and building the runner
(`build_runner`: weights cast and placed, both KV pools allocated)."""
from benchmark.startup import startup


def read(observed):
    up = startup(observed)
    return None if up is None else up["init_params"] + up["build_runner"]
