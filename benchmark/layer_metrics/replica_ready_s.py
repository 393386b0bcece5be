"""Wall seconds around serve.run(...) until the handle answered."""


def read(observed):
    return observed.get("ready_s")
