"""What `replica_ready_s` holds beyond the engine's own start: spawning the
worker, imports, reaching the chip, the controller's readiness round trip.
`ready_s` - (`init_params` + `build_runner` + `warmup`)."""
from benchmark.startup import startup


def read(observed):
    up = startup(observed)
    if up is None or observed.get("ready_s") is None:
        return None
    return observed["ready_s"] - (up["init_params"] + up["build_runner"]
                                  + up["warmup"])
