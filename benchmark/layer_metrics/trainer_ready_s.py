"""From the JaxTrainer.fit() call to the worker's first report."""


def read(observed):
    return observed.get("ready_s")
